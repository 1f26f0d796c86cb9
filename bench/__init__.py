"""The chip benchmark of the serving engine (see ``bench/run.py``)."""
