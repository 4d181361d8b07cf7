"""Paged, quantized KV-cache subsystem.

A shared page pool (int8 values + per-page scales) with per-sequence page
tables replaces the dense O(B·S_max) decode cache with an O(used pages)
one — the AIDA thesis (keep data resident, exploit lower precision)
applied to attention state.  See pool.py for the memory layout,
paged_attention.py for the decode kernel, alloc.py for the host-side
lifecycle, and api/session.py for the continuous-batching integration.
"""
from repro.kvstore.alloc import OutOfPages, PageAllocator, reclaimable_prefix
from repro.kvstore.paged_attention import (npp_bucket, paged_attention,
                                           paged_attention_chunk,
                                           paged_attention_pallas,
                                           paged_attention_pallas_chunk,
                                           paged_attention_xla,
                                           paged_attention_xla_chunk,
                                           resolve_paged,
                                           resolve_paged_chunk)
from repro.kvstore.pool import (GARBAGE_PAGE, NO_PAGE, PagedKV,
                                chunk_attention_mask,
                                copy_pages, dense_kv_bytes_per_token,
                                gather_kv, init_pool, init_table,
                                kv_bytes_per_token, layer_view, update,
                                update_chunk, update_layer)

__all__ = [
    "GARBAGE_PAGE", "NO_PAGE", "OutOfPages", "PageAllocator", "PagedKV",
    "chunk_attention_mask", "copy_pages",
    "dense_kv_bytes_per_token",
    "gather_kv", "init_pool", "init_table", "kv_bytes_per_token",
    "layer_view",
    "npp_bucket", "paged_attention", "paged_attention_chunk",
    "paged_attention_pallas", "paged_attention_pallas_chunk",
    "paged_attention_xla", "paged_attention_xla_chunk",
    "reclaimable_prefix", "resolve_paged", "resolve_paged_chunk",
    "update", "update_chunk", "update_layer",
]
