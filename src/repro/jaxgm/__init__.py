# TPU-adapted twin of repro.core: packed-matrix double simulation, frontier
# MJoin, vmapped query batches, and the shard_map distributed pipeline.
from .device_graph import DeviceGraph, from_host
from .encoding import QueryTensor, encode_batch, encode_query, jo_order
from .enumerate import MJoinCount, decode_tuples, mjoin_count
from .frontier import DeviceIntersector
from .matcher import JaxGM, JaxMatchResult
from .simulation import double_simulation, fb_sizes, rig_edge_counts

__all__ = [
    "DeviceGraph", "from_host",
    "QueryTensor", "encode_query", "encode_batch", "jo_order",
    "double_simulation", "fb_sizes", "rig_edge_counts",
    "mjoin_count", "MJoinCount", "decode_tuples", "DeviceIntersector",
    "JaxGM", "JaxMatchResult",
]
