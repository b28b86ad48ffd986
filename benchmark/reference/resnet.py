"""Plain reference for the bottleneck-ResNet configurations (He et al. 2015).

Float32 ``jax.numpy``/``lax`` under ``default_matmul_precision("highest")``,
no bf16, no fused kernel, nothing of ``deeplearning4j_tpu``. Weights and
batches are drawn here from the seed and the driver hands the SAME draws to
the program.

    conv   : NHWC x HWIO, SAME padding as XLA defines it, no bias
    bn     : training mode: y = (x - mean_b) / sqrt(var_b + eps) * gamma + beta
             over (N, H, W), var_b the biased batch variance
    block  : a 1x1(s) -> bn relu -> b 3x3 -> bn relu -> c 1x1 -> bn; shortcut the
             input, or 1x1(s) -> bn in a stage's first block; add; relu
    net    : stem 7x7/2 bn relu, max-pool 3x3/2 SAME, the stages, global average
             pool, dense + bias; loss = mean_rows(-sum(labels * log_softmax))
    Adam   : m, v moments, bias-corrected, p -= lr * m^ / (sqrt(v^) + eps)

Batch-norm couples the rows of a batch, so a step cannot be cut into blocks
of rows; it is computed layer by layer instead, each bottleneck (and the
stem) recomputed in the backward pass, so that the float32 step at the
cell's batch fits on the chip once the program's state is freed.

``product`` is the control's hook (``lowprec.FP8`` rounds both operands of
every convolution and of the dense product, and the gradient flowing back
into them, to scaled float8), ``rows`` plants the half-batch fault.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference.common import delta_norms, leaf_norms, seed_key
from reference.lowprec import EXACT


def layer_plan(config: dict):
    """[(name, kernel, stride, c_in, c_out)] of every convolution, in the
    program's node names (``<name>_conv`` / ``<name>_bn``)."""
    c = config["input_shape"][2]
    stem = config["stem"]
    plan = [("stem", stem["kernel"], stem["stride"], c, stem["filters"])]
    c = stem["filters"]
    for si, (n_blocks, (f1, f2, f3)) in enumerate(config["stages"]):
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"
            plan += [(f"{name}_a", 1, stride, c, f1), (f"{name}_b", 3, 1, f1, f2),
                     (f"{name}_c", 1, 1, f2, f3)]
            if bi == 0:
                plan.append((f"{name}_sc", 1, stride, c, f3))
            c = f3
    return plan, c


def make_weights(seed: int, config: dict):
    """{node: {leaf: array}} in one jitted call on the default device,
    float32: He-normal convolutions, gamma 1, beta 0, dense normal/sqrt(fan_in)
    with zero bias."""
    plan, c_last = layer_plan(config)
    classes = int(config["num_classes"])

    def draw(key):
        keys = jax.random.split(key, len(plan) + 1)
        out = {}
        for k, (name, ks, _, ci, co) in zip(keys, plan):
            std = math.sqrt(2.0 / (ks * ks * ci))
            out[f"{name}_conv"] = {
                "W": std * jax.random.normal(k, (ks, ks, ci, co), jnp.float32)}
            out[f"{name}_bn"] = {"gamma": jnp.ones((co,), jnp.float32),
                                 "beta": jnp.zeros((co,), jnp.float32)}
        out["out"] = {
            "W": jax.random.normal(keys[-1], (c_last, classes), jnp.float32)
            / math.sqrt(c_last),
            "b": jnp.zeros((classes,), jnp.float32)}
        return out

    return jax.jit(draw)(seed_key(seed))


def make_batches(seed: int, n: int, batch: int, config: dict):
    """(images (n, batch, H, W, C) float32 in [0, 1), one-hot labels
    (n, batch, classes) float32) on the host; every row different."""
    rng = np.random.default_rng([int(seed), 0x696D67])
    h, w, c = config["input_shape"]
    classes = int(config["num_classes"])
    x = rng.random((n, batch, h, w, c), dtype=np.float32)
    y = np.zeros((n, batch, classes), np.float32)
    idx = rng.integers(0, classes, (n, batch))
    np.put_along_axis(y, idx[..., None], 1.0, axis=-1)
    return x, y


def _conv(x, w, stride, product):
    return product(lambda x, w: lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST))(x, w)


def _bn(x, p, eps, relu):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + eps) * p["gamma"] + p["beta"]
    return jnp.maximum(y, 0.0) if relu else y


def loss(params, x, y, config, product=EXACT):
    eps = float(config["bn_eps"])

    def conv_bn(x, name, stride, relu):
        return _bn(_conv(x, params[f"{name}_conv"]["W"], stride, product),
                   params[f"{name}_bn"], eps, relu)

    @jax.checkpoint
    def stem(x):
        x = conv_bn(x, "stem", config["stem"]["stride"], True)
        k, s = config["stem_pool"]["kernel"], config["stem_pool"]["stride"]
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                                 (1, s, s, 1), "SAME")

    x = stem(x)
    for si, (n_blocks, _) in enumerate(config["stages"]):
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"

            def block(x, name=name, stride=stride, project=(bi == 0)):
                h = conv_bn(x, f"{name}_a", stride, True)
                h = conv_bn(h, f"{name}_b", 1, True)
                h = conv_bn(h, f"{name}_c", 1, False)
                sc = conv_bn(x, f"{name}_sc", stride, False) if project else x
                return jnp.maximum(h + sc, 0.0)

            x = jax.checkpoint(block)(x)
    pooled = jnp.mean(x, axis=(1, 2))
    z = product(lambda a, w: jnp.dot(a, w, precision=lax.Precision.HIGHEST))(
        pooled, params["out"]["W"]) + params["out"]["b"]
    return jnp.mean(-jnp.sum(y * jax.nn.log_softmax(z, axis=-1), axis=-1))


def train_steps(seed: int, config: dict, xs, ys, n_steps: int,
                product=EXACT, rows=None):
    """Follow ``n_steps`` Adam steps from the seed's weights on batches
    ``xs[i], ys[i]``; returns ``losses``, ``grad_norms`` (first step, per
    leaf) and ``delta_norms`` (after the last step, per leaf)."""
    hp = config["optimizer"]
    lr, b1, b2, eps = hp["learning_rate"], hp["b1"], hp["b2"], hp["eps"]
    tm = jax.tree_util.tree_map

    def step(params, m, v, count, x, y):
        l, g = jax.value_and_grad(loss)(params, x, y, config, product)
        count = count + 1
        m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        params = tm(lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
                    params, m, v)
        return params, m, v, count, l, leaf_norms(g)

    with jax.default_matmul_precision("highest"):
        step = jax.jit(step, donate_argnums=(0, 1, 2))
        params = make_weights(seed, config)
        m, v = tm(jnp.zeros_like, params), tm(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.float32)
        losses, grad_norms = [], None
        for i in range(n_steps):
            x, y = xs[i], ys[i]
            if rows is not None:
                x, y = x[rows], y[rows]
            params, m, v, count, l, gn = step(params, m, v, count,
                                              jnp.asarray(x), jnp.asarray(y))
            losses.append(float(l))
            if i == 0:
                grad_norms = {k: float(a) for k, a in gn.items()}
        delta = jax.jit(delta_norms)(params, make_weights(seed, config))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": {k: float(a) for k, a in delta.items()}}
