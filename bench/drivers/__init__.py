"""Part of the chip benchmark (see ``bench/run.py``)."""
