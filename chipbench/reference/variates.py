"""The random numbers an iteration or a scoring call consumes, drawn with
``jax.random`` from the key it was given, in the order the algorithm
(``ibp.py``) consumes them:

* sub-iteration l on shard p uses k = fold_in(fold_in(key, p), l),
  split in two: the sweep's (N_p, K) uniforms, and the tail's row chain;
* row r of a tail scan splits its chain key in four (next, bits, birth,
  spare): K_tail uniforms, then the birth key split in two, a
  Poisson(alpha / N) proposal and an acceptance uniform;
* the master sync uses fold_in(key, 101) split in two (the A noise,
  the pi Beta draws) and fold_in(key, 202) split in four (sigma_x²,
  sigma_a², alpha, p'); the next iteration's key is fold_in(key, 7);
* a scoring call splits its key over the S samples, each drawing
  (n_sweeps, K, B) uniforms.

Drawn on the device the run used, so the bits are the run's own.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("P", "L", "N_p", "K"))
def _sweep_uniforms(key, *, P, L, N_p, K):
    def one(p, l):
        ku, _ = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, p), l))
        return jax.random.uniform(ku, (N_p, K), dtype=jnp.float32)

    return jax.vmap(lambda p: jax.vmap(lambda l: one(p, l))(jnp.arange(L)))(
        jnp.arange(P))


@partial(jax.jit, static_argnames=("L", "N_p", "Kt"))
def _tail_variates(key, p_prime, alpha, N, *, L, N_p, Kt):
    lam = alpha.astype(jnp.float32) / N

    def rows(kt):
        def row(k, _):
            k2, kbits, kdish, _spare = jax.random.split(k, 4)
            u = jax.random.uniform(kbits, (Kt,), dtype=jnp.float32)
            kprop, kacc = jax.random.split(kdish)
            jp = jax.random.poisson(kprop, lam)
            ua = jax.random.uniform(kacc, (), dtype=jnp.float32)
            return k2, (u, jp, ua)

        _, out = jax.lax.scan(row, kt, None, length=N_p)
        return out

    def one(l):
        _, kt = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, p_prime), l))
        return rows(kt)

    return jax.vmap(one)(jnp.arange(L))


def fit_variates(key, p_prime: int, alpha, N: float, *, P: int, L: int,
                 N_p: int, K: int, Kt: int) -> dict:
    sw = np.asarray(_sweep_uniforms(key, P=P, L=L, N_p=N_p, K=K))
    u, jp, ua = jax.device_get(_tail_variates(
        key, jnp.int32(p_prime), jnp.asarray(alpha), jnp.float32(N),
        L=L, N_p=N_p, Kt=Kt))
    return {
        "sweep": [[sw[p, l] for l in range(L)] for p in range(P)],
        "tail": [{"u": u[l], "jprop": jp[l], "uacc": ua[l]} for l in range(L)],
    }


@partial(jax.jit, static_argnames=("K", "D", "P"))
def _master_variates(key, m, k_plus, N, a_sx, a_sa, a_alpha, *, K, D, P):
    k_a, k_pi = jax.random.split(jax.random.fold_in(key, 101))
    k_sx, k_sa, k_al, k_pp = jax.random.split(jax.random.fold_in(key, 202), 4)
    return {
        "eps": jax.random.normal(k_a, (K, D), dtype=jnp.float32),
        "beta": jax.random.beta(k_pi, jnp.maximum(m, 1e-6), 1.0 + N - m),
        "g_sx": jax.random.gamma(k_sx, a_sx + 0.5 * N * D),
        "g_sa": jax.random.gamma(k_sa, a_sa + 0.5 * k_plus * D),
        "g_al": jax.random.gamma(k_al, a_alpha + k_plus),
        "p_prime": jax.random.randint(k_pp, (), 0, P),
        "next_key": jax.random.key_data(jax.random.fold_in(key, 7)),
    }


def master_variates(key, Z_out, active_out, N: int, hyp: dict, *, P: int
                    ) -> dict:
    """The master's variates, given the post-sync Z and active set of the
    run under test (the Beta parameters are its feature counts)."""
    K = Z_out.shape[-1]
    m = np.sum(np.asarray(Z_out, np.float32).reshape(-1, K), axis=0)
    k_plus = np.float32(np.sum(np.asarray(active_out, np.float32)))
    f = jnp.float32
    return jax.device_get(_master_variates(
        key, jnp.asarray(m, f), f(k_plus), f(N), f(hyp["a_sx"]),
        f(hyp["a_sa"]), f(hyp["a_alpha"]), K=K, D=hyp["D"], P=P))


@partial(jax.jit, static_argnames=("S", "n_sweeps", "K", "B"))
def _score_uniforms(key, *, S, n_sweeps, K, B):
    keys = jax.random.split(key, S)
    return jax.vmap(lambda k: jax.random.uniform(
        k, (n_sweeps, K, B), dtype=jnp.float32))(keys)


def score_uniforms(key, *, S: int, n_sweeps: int, K: int, B: int):
    return np.asarray(_score_uniforms(key, S=S, n_sweeps=n_sweeps, K=K, B=B))
