"""Entries: how a cell drives the program. A workload file names one
(`entry`), and the harness imports harness/entries/<entry>.py, whose
run(rc) boots the system under test, runs the set-up, the measured window
and the comparison, and returns a Result."""
