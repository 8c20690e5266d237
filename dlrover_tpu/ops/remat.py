"""Named rematerialization policies (consumed by model configs and
the checkpoint/remat optimization; reference analog: atorch
activation_checkpointing.py policy selection), and the names under which
a block tags what its recomputation keeps."""


class Kept:
    """`jax.ad_checkpoint.checkpoint_name`s of arrays that a kernel made and
    that policy ``kernel_outputs`` keeps across a block's recomputation, so
    the kernel is not launched a second time in the backward pass. The tag
    sits where the array is made, inside a `custom_vjp`'s forward rule where
    it is a residual too (docs/observability.md)."""

    SELECTION = "indexer_selection"     # the int8 mask and its row log-sum-exp
    ATTENTION = "sparse_attn_out"       # the attention's output and log-sum-exp
    KL_GRADS = "indexer_kl_grads"       # what the KL term's backward reads
    LIGHTNING = "lightning_out"         # the output and the chunks' states
    BLOCKS = "block_selection"          # the int8 block mask
    BLOCK_SPARSE = "block_sparse_attn_out"  # its attention's out and lse

    # what ops/sparse_attention*.py tags; ops/linear_attention.py and
    # ops/block_sparse_attention.py tag the rest
    INDEXED = (SELECTION, ATTENTION, KL_GRADS)
    ALL = INDEXED + (LIGHTNING, BLOCKS, BLOCK_SPARSE)


def resolve_remat_policy(name: str):
    """Named rematerialization policy → jax.checkpoint_policies member.
    "full"/"nothing_saveable" recomputes everything; "dots"/"dots_saveable"
    keeps matmul outputs (cheaper backward, more memory); "kernel_outputs"
    keeps what the block tagged with a name of `Kept` and recomputes the
    rest: `nothing_saveable` for a block that tags nothing;
    "matmul_and_kernel_outputs" keeps those and every matmul without batch
    dimensions, i.e. the x @ W projections' outputs (not a batched product
    such as `block_select`'s scores, nor a kernel's own matmuls), so the
    backward pass launches no projection a second time, at the memory of
    their outputs."""
    import jax

    policies = {
        "": jax.checkpoint_policies.nothing_saveable,
        "full": jax.checkpoint_policies.nothing_saveable,
        "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
        "dots_with_no_batch_dims":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "kernel_outputs":
            jax.checkpoint_policies.save_only_these_names(*Kept.ALL),
        "matmul_and_kernel_outputs":
            jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(*Kept.ALL)),
    }
    if name not in policies:
        raise ValueError(f"unknown remat policy {name!r}; "
                         f"choose from {sorted(policies)}")
    return policies[name]
