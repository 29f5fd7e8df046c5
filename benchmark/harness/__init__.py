"""The benchmark's own modules: discovery, traffic, load generation, the
plain reference, the trace reduction, the byte model and the peaks.

Nothing here imports the program under test except the two entries
(harness/entries/), which boot it the way a deployment does."""
