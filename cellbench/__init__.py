"""cellbench: the on-chip benchmark of crdt-enc-tpu (see ``README.md`` here).

Everything that measures lives in this package, where a PR that changes the
program cannot change it: traffic generation, the plain reference, the
reduction from spans and traces to metrics, the table of peaks.  From the
program it takes only the system under test and its spans, counters and kernel
names.
"""
