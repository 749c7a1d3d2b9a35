"""repro: Jet/RDCA (Li et al., 2022) as a TPU-native JAX training/serving
framework.  See DESIGN.md for the paper->TPU mapping.

Module map
----------
- ``core``       Jet/RDCA primitives: buffer pool, READ window, recycle
                 model, escape ladder, DCQCN, Jet service facade, and the
                 single-receiver datapath simulator (``run_sim``).
- ``fabric``     multi-host Clos fabric: ``topology`` (leaf-spine graphs),
                 ``switch`` (output-queued, ECN + PFC), ``hosts`` (the
                 step-able ReceiverHost behind run_sim + DCQCN senders),
                 ``fabric`` (N-host driver -> per-host SimResults, victim
                 goodput, pause fan-out, incast FCT), ``scenarios``
                 (incast / all-to-all / storage mixes) and ``sweep`` (the
                 jax.vmap + lax.scan vectorized parameter-sweep engine
                 with a batched-numpy verification backend).
- ``kernels``    Pallas kernels (staged matmul, jet flash/decode
                 attention, mamba2 SSD) + jnp oracles.
- ``models``     architectures (transformer, MoE, SSM, xLSTM) behind one
                 ``api`` for train/prefill/decode.
- ``parallel``   sharding rules, jet staged collectives, int8+EF grad
                 compression, pipeline stages.
- ``train``      step construction (FSDP/TP/EP, accum microbatching) and
                 the training loop.
- ``serving``    batched engine + paged KV cache over the device pool.
- ``launch``     dry-run lowering/compile audit, HLO analysis, meshes.
- ``configs``    architectures x input shapes, and the paper's own
                 ``jet_testbed`` configuration.
- ``checkpoint`` elastic (reshardable) checkpointing.
- ``data``/``optim``  input pipeline; AdamW with int8 moments.
"""
__version__ = "1.1.0"
