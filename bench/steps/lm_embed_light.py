"""A light language-model consumer of packed rows: a token-embedding lookup for
every slot, masked by ``segment_ids > 0``, plus a term in the positions, reduced to
a scalar. The embedding table is T5-sized (32,000 x 2,048, bf16), and what the step
reduces is each slot's embedding summed over its width. So the lookup gathers from
the table's per-id row sums, made once from the table, and never materializes the
``[batch, length, 2,048]`` embeddings. That keeps the step light: the device reads
4 bytes a slot, not 4 KB, and the loader, not the step, sets the pace.

The table is made on the device from the seed; the step takes its row sums as an
argument. The ids are the store's, all below the vocabulary size, so the gather
skips its bounds handling.
"""
import jax
import jax.numpy as jnp

VOCAB = 32000
WIDTH = 2048


def build(ctx):
    field = ctx.config["field"]

    @jax.jit
    def row_sums(key):
        table = (jax.random.normal(key, (VOCAB, WIDTH), jnp.bfloat16)
                 * jnp.bfloat16(WIDTH ** -0.5))
        return jnp.sum(table, axis=1, dtype=jnp.float32)

    sums = jax.device_put(row_sums(ctx.key), ctx.replicated)

    @jax.jit
    def bench_step_lm_embed(sums, tokens, segment_ids, positions):
        mask = segment_ids > 0
        emb = sums.at[tokens].get(mode="promise_in_bounds")
        total = jnp.sum(jnp.where(mask, emb, 0.0))
        return (total / jnp.maximum(jnp.sum(mask), 1)
                + jnp.mean(positions.astype(jnp.float32)) * 1e-4)

    return lambda batch: bench_step_lm_embed(
        sums, batch[field], batch[field + "_segment_ids"], batch[field + "_positions"])
