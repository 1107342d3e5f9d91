"""The ops of the hot paths: Pallas TPU kernels, each beside the plain
jax.numpy body that is its numerical spec and its fallback, and the one
layout helper the mesh engines share.

* ``attention`` — ``causal_attention``, the softmax-attention core of the
  five language models: a fused forward and backward in which the
  ``[B, H, T, T]`` float32 scores never reach HBM, chosen where the program
  is lowered for a TPU and the shape fits; no option selects it.  Runs in
  ``ouro2p6b.silo4of256t1024``, ``lfm2moe24b.lora4of256t2048``,
  ``deepseekv2.lora4of256t4096``, ``cmdaplus.lora4of256long`` and
  ``xing4.lora4of256long``.
* ``rotary`` — ``apply_rotary``, the rotate-half rotary embedding in
  jax.numpy (the ouro and lfm2moe cells), and ``rotate_half``, the same
  result as one elementwise kernel pass, chosen as the attention kernels
  are: for heads as wide as the lanes (``cmdaplus.lora4of256long``) and for
  heads that divide them, several to a row of lanes - the 64-wide rotary
  part of latent attention's queries (``deepseekv2.lora4of256t4096``,
  ``xing4.lora4of256long``).
* ``hyper_connection`` — ``hc_read`` / ``hc_write``, the two passes a
  hyper-connection makes over a token's streams in front of and behind a
  sublayer (the RMS, the maps' projection and the read ``u`` in one; the
  write ``X'`` in the other) with backward rules of their own: four kernels
  over blocks of whole rows, chosen as the others are.  Runs in
  ``xing4.lora4of256long``.
* ``aggregate`` — ``flatten_stacked_tree`` / ``unflatten_to_tree``, stacked
  client trees as one padded f32 ``[C, N]`` matrix and back: no kernel; the
  mesh engines' krum / median / trimmed-mean defenses, which no cell runs.

Each kernel counts the path it took at trace time in
``ops_kernel_path_total{op, path}``.
"""
from fedml_tpu.ops.aggregate import flatten_stacked_tree, unflatten_to_tree
from fedml_tpu.ops.attention import causal_attention
from fedml_tpu.ops.hyper_connection import hc_read, hc_write
from fedml_tpu.ops.rotary import apply_rotary, rotate_half

__all__ = ["causal_attention", "apply_rotary", "rotate_half", "hc_read",
           "hc_write", "flatten_stacked_tree", "unflatten_to_tree"]
