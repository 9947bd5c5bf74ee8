"""Share of the traced window that the device spends under the named scope
``tp_codec`` (every MX quantize and dequantize of a TP collective), per
chip."""
from bench.metrics.scope_share_common import scope_share


def read(run):
    return scope_share(run, "tp_codec")
