"""served_bench — the benchmark of the served path (rig + sidecar on one chip).

Everything here is the yardstick: traffic generation, the plain reference,
the reduction from polls, counters and the profiler trace to metrics, the
table of peaks and the comparison that decides ``correct``.  From the program
it takes only the system under test (``consensus_tpu.deploy`` / ``.net``) and
the counters its processes report.  See README.md.
"""
