"""The benchmark of the FOS daemon on TPU chips (`python3 bench/run.py`)."""
