from . import flash_attention, fused_hop, ops, ref
from .backend import on_tpu, resolve_interpret
from .fused_adamw import adamw_update
from .fused_hop import hop_decode_add, hop_encode, hop_roundtrip_add
from .fused_reduce import fused_reduce
from .fused_rmsnorm import fused_rmsnorm

__all__ = ["ops", "ref", "fused_hop", "flash_attention", "adamw_update",
           "fused_reduce", "fused_rmsnorm", "hop_encode", "hop_decode_add",
           "hop_roundtrip_add", "on_tpu", "resolve_interpret"]
