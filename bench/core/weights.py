"""Random weights from the seed, made by the benchmark, in the served tree.

The benchmark, not the program, makes the weights, so the reference can
use the very same numbers without taking anything the program made.  One
jitted call builds the whole tree on the device, in float32 (the type the
program serves its weights in; it casts to bfloat16 inside the step).

The tree has the layout the program's serve step reads: ``embed [Vp, D]``,
``head [D, Vp]``, ``ln_f [D]``, and ``layers`` stacked on a leading layer
axis.  Norm gains are stored as offsets from one (the step multiplies by
``1 + ln``).  Gains, biases and padding are random too, so the comparison
with the reference covers every term, padding masks included.  Where the
configuration ties the output head to the embedding, ``head`` is a copy of
``embed`` transposed: the program keeps a separate head, and the copy makes
it compute the tied model.
"""
from __future__ import annotations

import functools
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# spread of the norm gains around one, and of the q/k/v biases
GAIN_SD = 0.1
BIAS_SD = 0.1


def as_run(cfg: dict) -> dict:
    """The configuration as the program computes it: the published values
    with each departure the program cannot help (``departures`` entries
    that name a ``key``) set to its ``as_run`` value."""
    out = dict(cfg)
    for d in cfg.get("departures", []):
        if "key" in d:
            out[d["key"]] = d["as_run"]
    return out


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def dims(cfg: dict) -> Dict[str, int]:
    """The sizes the served tree is laid out in, from a config file."""
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    d = dict(
        L=cfg["num_hidden_layers"], D=D, H=H,
        KV=cfg["num_key_value_heads"],
        hd=cfg.get("head_dim", D // H),
        V=cfg["vocab_size"], Vp=_round_up(cfg["vocab_size"], 256),
    )
    if cfg.get("num_experts"):
        d.update(E=cfg["num_experts"],
                 Ep=_round_up(cfg["num_experts"], 16),
                 k=cfg["num_experts_per_tok"],
                 F=cfg["moe_intermediate_size"],
                 Fs=cfg["shared_expert_intermediate_size"])
    else:
        d.update(F=cfg["intermediate_size"])
    return d


def _mat(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(
        jnp.float32(fan_in))


def _init(cfg: dict, key) -> Dict:
    cfg = as_run(cfg)
    n = dims(cfg)
    L, D, H, KV, hd = n["L"], n["D"], n["H"], n["KV"], n["hd"]
    ks = iter(jax.random.split(key, 32))

    def gain(*shape):
        return GAIN_SD * jax.random.normal(next(ks), shape, jnp.float32)

    mixer = {
        "wq": _mat(next(ks), (L, D, H * hd), D),
        "wk": _mat(next(ks), (L, D, KV * hd), D),
        "wv": _mat(next(ks), (L, D, KV * hd), D),
        "wo": _mat(next(ks), (L, H * hd, D), H * hd),
    }
    if cfg["attention_bias"]:
        for name, width in (("bq", H * hd), ("bk", KV * hd),
                            ("bv", KV * hd)):
            mixer[name] = BIAS_SD * jax.random.normal(
                next(ks), (L, width), jnp.float32)
    layers = {"mixer": mixer, "ln1": gain(L, D), "ln2": gain(L, D)}
    if "E" in n:
        Ep, F, Fs = n["Ep"], n["F"], n["Fs"]
        layers["moe"] = {
            "router": _mat(next(ks), (L, D, Ep), D),
            "w_gate": _mat(next(ks), (L, Ep, D, F), D),
            "w_up": _mat(next(ks), (L, Ep, D, F), D),
            "w_down": _mat(next(ks), (L, Ep, F, D), F),
            "shared": {
                "w_gate": _mat(next(ks), (L, D, Fs), D),
                "w_up": _mat(next(ks), (L, D, Fs), D),
                "w_down": _mat(next(ks), (L, Fs, D), Fs),
            },
        }
    else:
        F = n["F"]
        layers["mlp"] = {
            "w_gate": _mat(next(ks), (L, D, F), D),
            "w_up": _mat(next(ks), (L, D, F), D),
            "w_down": _mat(next(ks), (L, F, D), F),
        }
    if cfg["tie_word_embeddings"]:
        # at 1/sqrt(D), so that as the head it gives logits of unit
        # spread, as an untied head N(0, 1/D) does
        embed = _mat(next(ks), (n["Vp"], D), D)
        head = embed.T
    else:
        embed = jax.random.normal(next(ks), (n["Vp"], D), jnp.float32)
        head = _mat(next(ks), (D, n["Vp"]), D)
    return {
        "embed": embed,
        "head": head,
        "ln_f": gain(D),
        "layers": layers,
    }


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str):
    return jax.jit(functools.partial(_init, json.loads(cfg_json)))


def make(cfg: dict, seed: int):
    """The whole tree for ``seed``, built on the default device."""
    return _jitted(json.dumps(cfg, sort_keys=True))(jax_key(seed))


def jax_key(seed: int):
    """A JAX key from any whole-number seed (JAX keys take 32 bits)."""
    word = np.random.default_rng([int(seed) % 2**64, 0x5EED]).integers(
        0, 2**31)
    return jax.random.PRNGKey(int(word))
