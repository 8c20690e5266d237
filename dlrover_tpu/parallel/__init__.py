"""Parallelism layer: named-axis device meshes + sharding rules.

Capability parity: atorch's process-group zoo (create_parallel_group,
atorch/distributed/distributed.py:323; Megatron TP layer family,
modules/distributed_modules/layers.py) — re-designed TPU-first: one
`jax.sharding.Mesh` with named axes (data/fsdp/tensor/sequence/expert/pipe),
logical-axis rules instead of parallel module classes, and XLA-inserted
collectives over ICI/DCN.

The package imports nothing itself: the master imports its JAX-free
``planner`` and ``calibration`` in the launcher's process, which must stay
off JAX until the worker holds the chips. Import ``mesh``, ``sharding``
and the rest by their own names.
"""
