"""Host store to device: expert bytes copied (synchronous loads and
prefetches, each one expert's weights from the shapes) per token served."""
from perfbench import flops


def read(run):
    tokens = run.total("tokens")
    if not tokens:
        return None
    loads = run.total("on_demand_loads") + run.total("prefetched")
    return loads * flops.expert_bytes(run.cfg) / tokens / 1e6
