"""Tuning cache: persisted launch-parameter winners + deterministic defaults.

The autotune subsystem's contract with the rest of the repo lives here:

  * ``KernelConfig`` -- one kernel's launch parameters (block rows, lane
    width target, matmul tile, serving size-grid knobs).  Every field the
    kernels read is optional; ``None`` means "use the kernel's built-in
    heuristic", which is exactly what the pre-autotune code did.
  * ``DEFAULTS`` -- the deterministic configuration used whenever tuning
    is disabled or the cache has no entry.  These are the historical
    hardcoded values, so with autotuning off the system is bit-for-bit
    the pre-autotune system.
  * ``TuningCache`` -- a JSON-persisted map from
    ``(kernel, backend, dtype, size-class)`` to a winning config.  The
    repo commits ``default_cache.json`` (ref-backend winners from
    ``python -m repro.autotune --smoke --write-default``) so CI and fresh
    clones never depend on a tuning run.

Size classes are power-of-two buckets of the problem size (``p<k>`` holds
sizes in (2^(k-1), 2^k]), the same granularity the serving engine buckets
request lengths at; lookups fall back to the nearest tuned class before
falling back to the default config, so a cache tuned at two smoke shapes
still informs neighbouring sizes.

This module is stdlib-only on purpose: kernel ``ops.py`` entries import it
at module load, and it must never import back into ``repro.kernels``.
"""
from __future__ import annotations

import dataclasses
import json
import os

#: env switches (read once at first use; ``set_enabled`` overrides):
#:   REPRO_AUTOTUNE=1        -- consult the tuning cache
#:   REPRO_AUTOTUNE_CACHE=p  -- load winners from ``p`` instead of the
#:                              committed default_cache.json
ENV_ENABLE = "REPRO_AUTOTUNE"
ENV_CACHE = "REPRO_AUTOTUNE_CACHE"

DEFAULT_CACHE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "default_cache.json")

#: kernels the tuner knows how to search, and what each tunes:
#:   chain_diag / chain_apply / chain_project -- block rows + lane width
#:   chain_diag_q / chain_apply_q       -- same knobs, int16 Qm.n lane
#:                                         (cached under the format name
#:                                         as the dtype, e.g. "q8.7")
#:   chain_diag_batch / chain_apply_batch / chain_project_batch
#:   chain_diag_batch_q / chain_apply_batch_q
#:                                      -- batch-axis block rows
#:   matmul                             -- (bm, bn, bk) MXU tile
#:   rmsnorm                            -- block rows
#:   serving_grid                       -- size-bucket grid floor + waste cap
TUNABLE_KERNELS = ("chain_diag", "chain_apply", "chain_project",
                   "chain_diag_q", "chain_apply_q",
                   "chain_diag_batch", "chain_apply_batch",
                   "chain_project_batch", "chain_diag_batch_q",
                   "chain_apply_batch_q", "matmul", "rmsnorm",
                   "serving_grid")


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Launch parameters for one kernel family.  ``None`` fields defer to
    the kernel's built-in heuristic (the pre-autotune behaviour); only the
    fields a kernel reads are meaningful for it.  ``source`` records where
    the config came from: ``default`` (deterministic fallback), ``tuned``
    (fresh search winner this process), or ``cached`` (loaded winners
    file)."""
    kernel: str
    block_rows: int | None = None      # chain kernels / rmsnorm: grid row block
    lane_target: int | None = None     # chain_diag/chain_apply: lane width goal
    bm: int | None = None              # matmul output-tile rows
    bn: int | None = None              # matmul output-tile cols
    bk: int | None = None              # matmul K-panel depth
    grid_min_len: int | None = None    # serving size grid: floor
    grid_waste_cap: float | None = None  # serving size grid: padding cap
    source: str = "default"

    def key_fields(self) -> dict:
        """The tunable payload (everything except kernel/source) with
        ``None`` fields dropped -- what gets persisted and compared."""
        d = dataclasses.asdict(self)
        del d["kernel"], d["source"]
        return {k: v for k, v in d.items() if v is not None}

    def describe(self) -> str:
        """Compact ``k=v`` summary for reports."""
        fields = self.key_fields()
        body = ",".join(f"{k}={v}" for k, v in sorted(fields.items()))
        return f"{self.source}({body})" if body else self.source


#: the deterministic defaults: exactly the values the kernels hardcoded
#: before the autotune subsystem existed.  Tuning disabled == this table.
DEFAULTS: dict[str, KernelConfig] = {
    "chain_diag": KernelConfig("chain_diag", block_rows=256, lane_target=512),
    "chain_apply": KernelConfig("chain_apply", block_rows=256,
                                lane_target=512),
    "chain_project": KernelConfig("chain_project", block_rows=256,
                                  lane_target=512),
    # the fixed-point lane defaults to the float lane's launch shape:
    # same staging maths, half the bytes per lane
    "chain_diag_q": KernelConfig("chain_diag_q", block_rows=256,
                                 lane_target=512),
    "chain_apply_q": KernelConfig("chain_apply_q", block_rows=256,
                                  lane_target=512),
    # batch kernels: block_rows=None keeps the VMEM-budget heuristic in
    # kernels.util.stage_packed
    "chain_diag_batch": KernelConfig("chain_diag_batch"),
    "chain_apply_batch": KernelConfig("chain_apply_batch"),
    "chain_project_batch": KernelConfig("chain_project_batch"),
    "chain_diag_batch_q": KernelConfig("chain_diag_batch_q"),
    "chain_apply_batch_q": KernelConfig("chain_apply_batch_q"),
    "matmul": KernelConfig("matmul", bm=128, bn=128, bk=512),
    "rmsnorm": KernelConfig("rmsnorm", block_rows=256),
    "serving_grid": KernelConfig("serving_grid", grid_min_len=8,
                                 grid_waste_cap=0.5),
}


def size_class(n: int) -> str:
    """Power-of-two size-class label: ``p<k>`` holds n in (2^(k-1), 2^k].
    The serving engine buckets request lengths at the same granularity, so
    one tuned entry covers one padded-length class."""
    return f"p{max(0, int(n - 1).bit_length())}" if n > 0 else "p0"


def _class_index(label: str) -> int:
    return int(label[1:])


def cache_key(kernel: str, backend: str, dtype: str, n: int = 0) -> str:
    return f"{kernel}|{backend}|{dtype}|{size_class(n)}"


class TuningCache:
    """A map from cache keys to winning ``KernelConfig``s with JSON
    persistence.  Entries are stored sorted so the same winners always
    serialize to the same bytes (the determinism tests diff files)."""

    def __init__(self, entries: dict[str, KernelConfig] | None = None):
        self.entries: dict[str, KernelConfig] = dict(entries or {})

    # -- lookup --------------------------------------------------------------

    def get(self, kernel: str, backend: str, dtype: str = "float32",
            n: int = 0) -> KernelConfig | None:
        """Exact-key lookup, then nearest tuned size-class for the same
        (kernel, backend, dtype), else None."""
        exact = self.entries.get(cache_key(kernel, backend, dtype, n))
        if exact is not None:
            return exact
        prefix = f"{kernel}|{backend}|{dtype}|"
        want = _class_index(size_class(n))
        best = None
        for key, cfg in self.entries.items():
            if not key.startswith(prefix):
                continue
            dist = abs(_class_index(key.rsplit("|", 1)[1]) - want)
            # deterministic tie-break: prefer the smaller class
            rank = (dist, _class_index(key.rsplit("|", 1)[1]))
            if best is None or rank < best[0]:
                best = (rank, cfg)
        return best[1] if best else None

    def put(self, kernel: str, backend: str, dtype: str, n: int,
            config: KernelConfig) -> None:
        self.entries[cache_key(kernel, backend, dtype, n)] = config

    def __len__(self) -> int:
        return len(self.entries)

    # -- persistence ---------------------------------------------------------

    def to_json(self) -> str:
        payload = {key: dict(sorted(cfg.key_fields().items()))
                   for key, cfg in sorted(self.entries.items())}
        return json.dumps({"version": 1, "entries": payload}, indent=1,
                          sort_keys=True) + "\n"

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    @classmethod
    def load(cls, path: str) -> "TuningCache":
        with open(path) as f:
            doc = json.load(f)
        entries = {}
        for key, fields in doc.get("entries", {}).items():
            kernel = key.split("|", 1)[0]
            entries[key] = KernelConfig(kernel=kernel, source="cached",
                                        **fields)
        return cls(entries)


# -- module state: the process-wide cache + enable switch --------------------

_ENABLED: bool | None = None          # None -> read env on first use
_CACHE: TuningCache | None = None
_CACHE_PATH: str | None = None        # None -> env or committed default


def enabled() -> bool:
    if _ENABLED is not None:
        return _ENABLED
    return os.environ.get(ENV_ENABLE, "") in ("1", "true", "yes")


def set_enabled(on: bool | None) -> None:
    """Flip cache consultation on/off (``None`` re-reads the env var).
    NOTE: compiled plans capture their config at trace time -- use
    ``repro.autotune.set_enabled``, which also clears the plan caches."""
    global _ENABLED
    _ENABLED = on


def set_cache_path(path: str | None) -> None:
    """Point the process at a different winners file (``None`` -> env /
    committed default) and drop the loaded cache."""
    global _CACHE_PATH, _CACHE
    _CACHE_PATH = path
    _CACHE = None


def set_cache(cache: TuningCache | None) -> None:
    """Install an in-memory cache directly (tests, fresh tuning runs)."""
    global _CACHE
    _CACHE = cache


def the_cache() -> TuningCache:
    """The process-wide winners cache, loaded lazily from (in order)
    ``set_cache_path``, ``$REPRO_AUTOTUNE_CACHE``, the committed
    ``default_cache.json``, else empty."""
    global _CACHE
    if _CACHE is None:
        path = _CACHE_PATH or os.environ.get(ENV_CACHE) or DEFAULT_CACHE_PATH
        _CACHE = TuningCache.load(path) if os.path.exists(path) \
            else TuningCache()
    return _CACHE


def config_for(kernel: str, backend: str, dtype: str = "float32",
               n: int = 0) -> KernelConfig:
    """THE lookup the integrated consumers call: the cached winner for
    (kernel, backend, dtype, size-class) when tuning is enabled, else the
    deterministic default.  Unknown kernels get an all-``None`` config
    (every field defers to the kernel heuristic)."""
    default = DEFAULTS.get(kernel, KernelConfig(kernel))
    if not enabled():
        return default
    hit = the_cache().get(kernel, backend, dtype, n)
    return hit if hit is not None else default


def merge(fallback: KernelConfig, override: KernelConfig) -> KernelConfig:
    """``override`` with its ``None`` fields filled from ``fallback``."""
    fields = {f.name: getattr(override, f.name)
              if getattr(override, f.name) is not None
              else getattr(fallback, f.name)
              for f in dataclasses.fields(KernelConfig)
              if f.name not in ("kernel", "source")}
    return KernelConfig(kernel=override.kernel, source=override.source,
                        **fields)
