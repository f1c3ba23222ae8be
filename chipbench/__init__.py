"""The FliX chip benchmark (see ``run.py``)."""
