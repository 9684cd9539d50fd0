"""The chip benchmark of the federated round engine (see ``run.py``)."""
