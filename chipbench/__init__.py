"""A chip benchmark for the Free Join engine, driven by data: see
harness.py for how a cell finds its configuration, traffic, driver and
metric readers, and run.py for the command."""
