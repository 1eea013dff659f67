"""The benchmark of the served path: see PERF.md and BENCHMARK.json.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that decides a number lives here and not in the program: traffic
generation (``traffic.py``), the corpus (``corpus.py``), each configuration's
plain reference (``configs/<name>/reference.py``), the comparison that decides
``correct`` (``correctness.py``), the reduction from a profiler trace
(``trace_reduce.py``), the bytes a search needs (``search_bytes.py``) and the
table of peaks (``peaks.json``). A configuration, a traffic mix and a
per-layer metric are files found by the names ``BENCHMARK.json`` gives them
(``loader.py``); adding one edits no file that is already here.
"""
