"""Share of the traced window that the device spends under the named scope
``paged_attention`` (the paged KV read, attention and append), per chip."""
from bench.metrics.scope_share_common import scope_share


def read(run):
    return scope_share(run, "paged_attention")
