"""On-chip benchmark of the spiking-network simulator (see ``run.py``)."""
