"""Ragged serving kernels (reference: deepspeed/inference/v2/kernels/ —
blocked_flash, linear_blocked_kv_rotary, moe_gather/moe_scatter, logits_gather).

TPU equivalents live here as Pallas kernels + XLA-native ops: ``ragged_ops.py``
(K/V pages), ``mla_ops.py`` (latent pages); ``page_ops.py`` lists each cache
kind's operations for the paged forward.
"""
