"""Step costs of each model family: ``<model_type>.py`` counts the FLOPs and
bytes of its serving steps (see ``bench/flops.py``)."""
