"""The chip benchmark: one cell per run, driven by the data files here
(see ``run.py``)."""
