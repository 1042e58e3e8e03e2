"""Plain reference for the toy classifier: dense layers and ReLUs in
float32 ``jax.numpy``.  The weights are the served ones: the program's
module makes the same tree from the seed here on the CPU."""

from __future__ import annotations


def make_params(model: dict, seed: int):
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.mlp import MLPClassifier

    module = MLPClassifier(num_classes=model["num_classes"])
    return module.init(jax.random.key(seed), jnp.zeros((1, model["features"]), jnp.float32))


def scores(params, model: dict, rows):
    import jax
    import jax.numpy as jnp

    layers = params["params"]
    x = jnp.asarray(rows, jnp.float32)
    for name in sorted(k for k in layers if k != "head"):
        x = jax.nn.relu(x @ layers[name]["kernel"] + layers[name]["bias"])
    return x @ layers["head"]["kernel"] + layers["head"]["bias"]
