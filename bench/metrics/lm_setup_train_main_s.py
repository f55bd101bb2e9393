"""Seconds of the LM cell's set-up spent in ``launch/train.main``:
``setup_train_main_s``'s reading of the ``dwfl.main`` span (the data, the
initial buffer, the channel and the trainer, with no round run)."""
from bench.metrics.setup_train_main_s import read  # noqa: F401
