"""Random weights from ``--seed``, made on the device in one jitted call, in
the type they are served in.

The tree has the layout the program's ``init_specs`` declares (its leaves'
paths, shapes and dtypes); the values are the benchmark's own:

  * matrices   normal, std 1 / sqrt(fan_in), so each projection keeps the
               scale of its input;
  * biases     normal, std 0.1, so the bias paths change the result;
  * norm gains 1 + normal(0.1); norm biases normal(0.1): the leaves of
    any parent whose name holds ``norm`` (block norms, the final norm, a
    latent or per-head q/k norm);
  * the embedding, where it is also the unembedding (tied), normal with
    std LOGIT_STD / sqrt(d_model): logits over a normalised hidden state
    then have a standard deviation near LOGIT_STD, at any width;
    an untied embedding is normal(1) and the head normal(LOGIT_STD /
    sqrt(d_model)).
"""

from __future__ import annotations

import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_STD = 4.0


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0]) >> 1),
                              int(words[1]) >> 1)


def _path_names(path) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _leaf(key, names: Tuple[str, ...], shape, dtype, d_model: int,
          tied: bool):
    name, parent = names[-1], names[-2] if len(names) > 1 else ""
    normal = lambda std: jax.random.normal(key, shape, jnp.float32) * std
    if "norm" in parent:
        x = 1.0 + normal(0.1) if name == "w" else normal(0.1)
    elif name == "embed":
        x = normal(LOGIT_STD / math.sqrt(d_model) if tied else 1.0)
    elif name == "lm_head":
        x = normal(LOGIT_STD / math.sqrt(d_model))
    elif len(shape) - (1 if names[0] == "group" else 0) <= 1:
        x = normal(0.1)                          # a bias
    else:
        x = normal(1.0 / math.sqrt(shape[-2]))
    return x.astype(dtype)


def make(shapes: Any, seed: int, d_model: int, tied: bool) -> Any:
    """Weights for the tree of ``jax.ShapeDtypeStruct`` leaves ``shapes``.
    ``group`` leaves carry a leading layer axis."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(key):
        leaves = [_leaf(jax.random.fold_in(key, i), _path_names(p),
                        sd.shape, sd.dtype, d_model, tied)
                  for i, (p, sd) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.block_until_ready(jax.jit(gen)(seed_key(seed)))
