"""``decoder.prefill`` as the fixed-batch path runs it, for the tests that
look at a prefill alone: into a pool of zeros made here, a slot a prompt."""

import jax.numpy as jnp

from pytorch_zappa_serverless_tpu.models import decoder


def prefill(fam, params, tokens, lengths, total, dtype=jnp.bfloat16,
            adapter_idx=None):
    """(logits [B, V], *the leaves of a fresh pool of B slots)."""
    B = tokens.shape[0]
    return decoder.prefill(fam, params, tokens, lengths,
                           decoder.zero_cache(fam, B, total, dtype),
                           jnp.arange(B), dtype, adapter_idx)
