"""A reference module for the tests, named by a configuration's
``"reference"`` key: the uniform stack of ``bench/reference.py``, run layer
by layer in a Python loop over ``m["num_layers"]``, a field that the
default module's ``FIELDS`` lacks.  ``SEEN`` keeps each ``m`` it was given.
"""

import jax

import reference

FIELDS = reference.FIELDS + ("num_layers",)
SEEN = []


def hidden(params, tokens, m, mode="f32"):
    SEEN.append(dict(m))
    mm, es = reference._products(mode)
    x = params["embed"][tokens].astype(reference.F32)
    for i in range(m["num_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["groups"]["b0"])
        x = reference.block(x, lp, m, mm, es)
    return reference._rmsnorm(
        x, params["final_norm"]["scale"].astype(reference.F32), m["norm_eps"])
