"""The benchmark's one traffic generator: everything a mix's data file
parameterises, drawn from ``--seed``.  Shapes and counts come from the
file alone, so every seed builds the same programs.

* ``k_schedule``: local step counts K_i = max(round(N(k_mean, k_var)),
  k_min) for ``k_rounds`` rounds of M clients, from the mix's fixed
  ``k_seed`` (never ``--seed``), repeated round after round.
* ``token_table``: per client, ``seqs_per_client`` sequences of seq + 1
  tokens from a Zipf(``zipf_a``) unigram law whose mass is boosted
  ``topic_boost``-fold on the client's own vocabulary band (1 /
  ``topic_bands`` of the vocabulary), so clients differ in what they
  see; inverse-CDF sampling on the device.  The law is that of the
  program's ``data/synthetic.token_stream``.
* ``round_rows``: which sequences each client trains on in each local step
  of each round: rows within one (round, client) are all different.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, salt])


def k_schedule(tr: dict) -> np.ndarray:
    """(k_rounds, M) int32 K_i, the same for every seed."""
    r = np.random.default_rng(tr["k_seed"])
    ks = r.normal(tr["k_mean"], np.sqrt(tr["k_var"]),
                  (tr["k_rounds"], tr["clients"])).round()
    return np.maximum(ks, tr["k_min"]).astype(np.int32)


def token_table(key, tr: dict, vocab: int):
    """(M, seqs_per_client, seq + 1) int32 on the device."""
    import jax
    import jax.numpy as jnp
    m, n, s = tr["clients"], tr["seqs_per_client"], tr["seq"] + 1
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    base = ranks ** (-tr["zipf_a"])
    band = vocab // tr["topic_bands"]

    @jax.jit
    def make(key):
        rows = []
        for i in range(m):
            start = (i * band) % max(vocab - band, 1)
            boost = jnp.zeros((vocab,)).at[start:start + band].set(1.0)
            p = base * (1.0 + tr["topic_boost"] * boost)
            cdf = jnp.cumsum(p / jnp.sum(p))
            u = jax.random.uniform(jax.random.fold_in(key, i), (n, s))
            rows.append(jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1))
        return jnp.stack(rows).astype(jnp.int32)

    return make(key)


def round_rows(seed: int, tr: dict, k_max: int, rounds: int) -> np.ndarray:
    """(rounds, M, k_max, batch) int32 sequence indices; within a (round,
    client) no index repeats."""
    r = rng(seed, 1)
    m, b, n = tr["clients"], tr["batch"], tr["seqs_per_client"]
    if k_max * b > n:
        raise ValueError(f"{k_max}×{b} rows per round exceed {n} sequences")
    out = np.empty((rounds, m, k_max, b), np.int32)
    for t in range(rounds):
        for i in range(m):
            out[t, i] = r.permutation(n)[:k_max * b].reshape(k_max, b)
    return out

