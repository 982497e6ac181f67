"""Repo tooling. A package so `python -m tools.analyze` resolves;
the sibling scripts (load_sweep.py, obs_report.py, ...) stay directly
runnable."""
