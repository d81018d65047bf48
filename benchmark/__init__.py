"""The repo's yardstick (BENCHMARK.json): one command, cells found by name.

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: the runners, the reduction from trace to metrics
(``trace.py``), the analytic FLOP counts (``flops.py``), the table of peaks
(``peaks.py``), each configuration's plain reference (``reference/``) and
the comparison that decides ``correct``. From the program the benchmark
takes only the system under test and its counters.

A configuration is ``configs/<name>.json``, a traffic mix (or training job)
is ``traffic/<name>.json`` and names its runner ``runners/<runner>.py``, a
per-layer metric is ``layers/<name>.py``: a later PR adds files and
``BENCHMARK.json`` entries and edits nothing here.
"""
