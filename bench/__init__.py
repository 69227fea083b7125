"""On-chip benchmark of the AdHash engine: one cell (a deployment under a
traffic mix) per run of ``python3 bench/run.py``; see ``run.py``."""
