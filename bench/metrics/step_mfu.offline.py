"""Whole-step model FLOP utilisation in the traced window, percent of the
chips' bf16 peak (offline cells)."""
from bench.metrics.step_mfu_common import step_mfu


def read(run):
    return step_mfu(run)
