"""Chip benchmark of the MPI-over-sPIN simulator (see ``run.py``)."""
