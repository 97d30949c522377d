"""Pallas TPU kernels for the compute hot spots of the model plane.

Foreactor itself is a host-I/O technique (no device-kernel contribution in
the paper); these kernels exist because the *framework* needs perf-critical
device compute:

* :mod:`repro.kernels.flash_attention` — blockwise online-softmax attention
  (GQA/MQA-aware, causal block skipping), targets the MXU with 128-aligned
  q/k blocks held in VMEM.
* :mod:`repro.kernels.decode_attention` — flash-decode for single-token
  queries against long KV caches (streamed KV blocks, running max/sum).
* :mod:`repro.kernels.mamba2_scan` — chunked SSD scan: dense intra-chunk
  matmuls on the MXU + carried inter-chunk state.
* :mod:`repro.kernels.rwkv6_scan` — RWKV6 (Finch) data-dependent-decay
  recurrence, chunked the same way.

Each kernel ships ``<name>.py`` (pl.pallas_call + BlockSpec), a jitted
wrapper in :mod:`repro.kernels.ops`, and a pure-jnp oracle in
:mod:`repro.kernels.ref`.  On this CPU container kernels are validated with
``interpret=True``; off the TPU, model code defaults to the
memory-efficient jnp reference implementations.
"""
