"""Find the benchmark's parts by name: ``<kind>/<name>.py`` under this
directory, loaded as a module.  Metrics (``metrics/``), request families
(``families/``) and window loops (``loops/``) are found this way, so a
later cell brings its parts as new files and edits none."""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def module(kind: str, name: str):
    """The module in ``<kind>/<name>.py``; a missing file raises."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in (HERE / kind).glob("*.py"))
        raise KeyError(f"no {kind} file {name!r}; known: {known}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
