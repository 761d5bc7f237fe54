"""The chip benchmark of the geometry server.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it
is started on and prints one JSON result line.  Everything a cell is
made of sits in files of its own, found by name:

  * ``configs/<config>.json`` -- the deployment (sizes, guarantees,
    the limits of the comparison that decides ``correct``);
  * ``traffic/<mix>.json``    -- the mix's parameters, read by the one
    generator in ``traffic.py``;
  * ``families/<family>.py``  -- how a configuration's requests are made
    (``flushes``, ``SMALL``, optionally its own ``Tally``);
  * ``loops/<loop>.py``       -- how a window serves them (``server``,
    ``warm``, ``window``);
  * ``metrics/<metric>.py``   -- one reader per metric.

The yardstick lives here too, apart from the program: the float64
reference (``reference.py``), the trace reduction (``xplane.py``), the
percentile, payload-byte and peak arithmetic (``yardstick.py``).
"""
