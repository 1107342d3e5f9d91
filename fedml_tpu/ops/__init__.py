"""Pallas TPU kernels, each beside the plain jax.numpy body that is its
numerical spec and its fallback.

* ``aggregate`` — the server-side aggregation hot path.  The reference's
  server aggregation is a Python loop over state_dict keys on CPU
  (FedAVGAggregator.py:59-88); XLA already turns our tree-level weighted
  mean into fused HBM-bandwidth kernels, and these kernels go one step
  further: the entire cohort aggregation — including the robust norm-clip
  pipeline — runs as a single pass over the stacked client weights in VMEM
  tiles, with the reduction on the MXU.  Behind ``pallas_agg=True``.
* ``groupnorm`` — a fused GroupNorm forward and backward; measured slower
  than XLA's own fusions on the chip, kept as a building block (its
  docstring has the numbers).
* ``attention`` — ``causal_attention``, the softmax-attention core of both
  language models (models/looped_lm.py, models/lfm2_moe.py): a fused
  forward and backward in which the ``[B, H, T, T]`` float32 scores never
  reach HBM, chosen where the program is lowered for a TPU and the shape
  fits; no option selects it.
* ``rotary`` — ``apply_rotary``, the language models' rotate-half rotary
  embedding, and ``rotate_half``, the same result as one elementwise kernel
  pass for heads as wide as the lanes (models/cohere2_moe.py), chosen as the
  attention kernels are.

Each op counts the path it took at trace time in
``ops_kernel_path_total{op, path}``.
"""
from fedml_tpu.ops.aggregate import (flatten_stacked_tree,
                                     robust_weighted_mean_pallas,
                                     unflatten_to_tree,
                                     weighted_mean_pallas)
from fedml_tpu.ops.attention import causal_attention
from fedml_tpu.ops.rotary import rotate_half

__all__ = ["weighted_mean_pallas", "robust_weighted_mean_pallas",
           "flatten_stacked_tree", "unflatten_to_tree", "causal_attention",
           "rotate_half"]
