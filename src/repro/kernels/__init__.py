"""Pallas TPU kernels for the solver's hot spots (+ jnp oracles).

* ``bitset_ops`` — batched induced-subgraph degrees (B&B branching).

The subpackage ships ``kernel.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jit'd dispatch wrapper) and ``ref.py`` (pure-jnp oracle);
kernels are validated with interpret=True on CPU and target TPU natively.
"""
