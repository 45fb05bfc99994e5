from .ops import (decode_attention, fold_kv, gather_pages,
                  paged_decode_attention, ring_kv_len, ring_positions,
                  unfold_kv)
from .ref import decode_attention_ref
from .kernel import decode_attention_pallas, paged_decode_attention_pallas
__all__ = ["decode_attention", "decode_attention_ref",
           "decode_attention_pallas", "paged_decode_attention",
           "paged_decode_attention_pallas", "gather_pages",
           "ring_kv_len", "ring_positions", "fold_kv", "unfold_kv"]
