"""``ref_layers.py`` with the last layer left out: a wrong reference, which
has to make a sound run come out not correct."""

import jax

import reference

FIELDS = reference.FIELDS + ("num_layers",)


def hidden(params, tokens, m, mode="f32"):
    mm, es = reference._products(mode)
    x = params["embed"][tokens].astype(reference.F32)
    for i in range(m["num_layers"] - 1):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["groups"]["b0"])
        x = reference.block(x, lp, m, mm, es)
    return reference._rmsnorm(
        x, params["final_norm"]["scale"].astype(reference.F32), m["norm_eps"])
