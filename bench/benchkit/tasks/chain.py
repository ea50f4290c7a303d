"""Chain labeling data (OCR-like), made on the device from a seed.

The distributions are those of ``repro.data.synthetic.ocr_like``: label
prototypes ``N(0, 1)`` in ``f`` dimensions, word lengths
``Poisson(mean_len)`` clipped to ``[min_len, max_len]``, a first label
uniform over ``C``, then a Markov chain whose transition ``a -> b`` has
weight ``trans_strength * exp(-((a - b) mod C)^2 / 2)``, and features
``prototype[y_l] + noise * N(0, 1)``; positions past a word's length are
zero.  The words are drawn from the configuration's fixed ``data_seed``
and put in an order drawn from the run's seed, in one jitted call: every
seed trains on the same words (the same work), in another order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import flops
from ..reference.chain import ChainTask


def transition_probs(num_labels: int, strength: float) -> np.ndarray:
    a = np.arange(num_labels)
    logits = strength * np.exp(-0.5 * ((a[:, None] - a[None, :])
                                       % num_labels) ** 2)
    return logits / logits.sum(axis=1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("n", "f", "C", "mean_len", "min_len",
                                    "max_len", "noise", "strength"))
def _words(key, order_key, *, n, f, C, mean_len, min_len, max_len, noise,
           strength):
    k_proto, k_len, k_first, k_chain, k_noise = jax.random.split(key, 5)
    protos = jax.random.normal(k_proto, (C, f), jnp.float32)
    lengths = jnp.clip(jax.random.poisson(k_len, mean_len, (n,)),
                       min_len, max_len).astype(jnp.int32)
    log_t = jnp.log(jnp.asarray(transition_probs(C, strength), jnp.float32))
    y0 = jax.random.randint(k_first, (n,), 0, C, jnp.int32)

    def next_label(prev, k):
        y = jax.random.categorical(k, log_t[prev], axis=-1).astype(jnp.int32)
        return y, y

    _, rest = jax.lax.scan(next_label, y0,
                           jax.random.split(k_chain, max_len - 1))
    y = jnp.concatenate([y0[None], rest], axis=0).T          # (n, max_len)
    mask = jnp.arange(max_len)[None, :] < lengths[:, None]
    x = protos[y] + noise * jax.random.normal(k_noise, (n, max_len, f),
                                              jnp.float32)
    order = jax.random.permutation(order_key, n)
    return {"x": jnp.where(mask[..., None], x, 0.0)[order],
            "y": jnp.where(mask, y, 0)[order],
            "mask": mask[order]}


def make_data(cfg: dict, key, n: int | None = None) -> dict:
    """The configuration's words on the device, in the order ``key``
    draws (``n`` overrides the configuration's count, for a pool of
    requests)."""
    return _words(jax.random.PRNGKey(int(cfg["data_seed"])), key,
                  n=int(cfg["n"] if n is None else n), f=int(cfg["f"]),
                  C=int(cfg["num_labels"]), mean_len=float(cfg["mean_len"]),
                  min_len=int(cfg["min_len"]), max_len=int(cfg["max_len"]),
                  noise=float(cfg["noise"]),
                  strength=float(cfg["trans_strength"]))


def spec(cfg: dict):
    """The program's task description for this configuration."""
    from repro.core.oracles.chain import ChainSpec

    return ChainSpec(int(cfg["num_labels"]))


def reference(host: dict, cfg: dict, prec):
    return ChainTask(host["x"], host["y"], host["mask"],
                     int(cfg["num_labels"]), prec)


def dim(cfg: dict) -> int:
    C = int(cfg["num_labels"])
    return C * int(cfg["f"]) + C * C


def oracle_ops(cfg: dict, mean_len: float) -> float:
    return flops.chain_oracle(mean_len, int(cfg["f"]),
                              int(cfg["num_labels"]))
