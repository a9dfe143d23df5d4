"""The probing model under squared L2, copied from the paper's equations as
the program has them (``core/probing.py``): the query, scale-normalised,
through phi_q; its squared-L2 distances to the centroids, each divided by
their row mean less one, through phi_i; both through phi_p to one sigmoid
per partition.

``work.probe_mask`` finds this file by the configuration's
``dataset.metric``; a metric's copy mirrors the features the program uses
under that metric.
"""
import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _mlp(layers, x, final_act=True):
    for i, layer in enumerate(layers):
        x = jnp.dot(x, layer["w"], precision=_HIGHEST) + layer["b"]
        if final_act or i + 1 < len(layers):
            x = jax.nn.relu(x)
    return x


@jax.jit
def probs(params, cents, q):
    """[nq, B] probability that each partition holds a neighbour of q."""
    cd = (jnp.sum(q * q, -1, keepdims=True) - 2.0 * jnp.dot(q, cents.T, precision=_HIGHEST)
          + jnp.sum(cents * cents, -1)[None, :])
    qn = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-6)
    feat = cd / (jnp.mean(cd, axis=-1, keepdims=True) + 1e-6) - 1.0
    x = jnp.concatenate([_mlp(params["phi_q"], qn), _mlp(params["phi_i"], feat)], -1)
    return jax.nn.sigmoid(_mlp(params["phi_p"], x, final_act=False))
