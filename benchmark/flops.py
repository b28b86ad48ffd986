"""Operations and bytes the algorithms need, computed from shapes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. A multiply-add is 2 operations. Training is counted as 3 x the
forward pass (forward, gradient by the inputs, gradient by the weights);
operations a program chooses to recompute are NOT counted, so rematerialized
blocks and a recomputing backward kernel lower the share they report.
"""

from __future__ import annotations

import importlib


def resolve(name: str):
    """The counting function a metric file names: ``fn`` of this file, or
    ``module:fn`` of a file a later PR adds beside it."""
    module, _, fn = name.rpartition(":")
    return getattr(importlib.import_module(module or __name__), fn)


def lm_train_flops_per_token(config: dict, seq: int) -> float:
    """Decoder-only LM, one training token at context ``seq``.

    Forward, per token: every weight matrix of the blocks is used once
    (2 x its parameters: QKV d*3d, output d*d, MLP d*f + f*d), the tied head
    d*V once, and causal attention does QK^T and PV against the seq/2 keys a
    token sees on average (2 products x 2 x d x seq/2 = 2*d*seq per layer).
    The embedding lookup, norms, GELU and softmax are not matmul work and are
    left out (under 1 % at these widths). gpt2-medium at seq 1024:
    3 x (2 x 301,989,888 + 2 x 51,463,168 + 24 x 2,097,152) = 2.2717 GFLOP.
    """
    d, f = int(config["n_embd"]), int(config.get("n_inner") or 4 * config["n_embd"])
    layers, vocab = int(config["n_layer"]), int(config["vocab_size"])
    block_params = layers * (4 * d * d + 2 * d * f)
    forward = 2 * block_params + 2 * d * vocab + layers * 2 * d * seq
    return 3.0 * forward


def resnet_conv_shapes(config: dict):
    """Yield (name, out_h, out_w, kh, kw, c_in, c_out) for every convolution
    and the final dense layer (as a 1x1 'conv' on a 1x1 map) of a bottleneck
    ResNet configuration, strides of a stage's first block in its first 1x1
    convolution and its projection shortcut (He et al. 2015, Table 1)."""
    h, w, c = config["input_shape"]
    stem = config["stem"]
    h, w = -(-h // stem["stride"]), -(-w // stem["stride"])
    yield ("stem", h, w, stem["kernel"], stem["kernel"], c, stem["filters"])
    c = stem["filters"]
    pool = config["stem_pool"]
    h, w = -(-h // pool["stride"]), -(-w // pool["stride"])
    for si, (n_blocks, (f1, f2, f3)) in enumerate(config["stages"]):
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            h, w = -(-h // stride), -(-w // stride)
            name = f"s{si}b{bi}"
            yield (name + "_a", h, w, 1, 1, c, f1)
            yield (name + "_b", h, w, 3, 3, f1, f2)
            yield (name + "_c", h, w, 1, 1, f2, f3)
            if bi == 0:
                yield (name + "_sc", h, w, 1, 1, c, f3)
            c = f3
    yield ("out", 1, 1, 1, 1, c, int(config["num_classes"]))


def resnet_forward_macs_per_image(config: dict) -> float:
    """Multiply-adds of one forward image: sum over the convolutions of
    out_h*out_w*kh*kw*c_in*c_out. ResNet-50 at 224x224: 3.86e9, inside the
    paper's '3.8e9 FLOPs' (its FLOPs are multiply-adds) and under the 4.1e9
    of the variant that strides in the 3x3."""
    return float(sum(oh * ow * kh * kw * ci * co
                     for _, oh, ow, kh, kw, ci, co in resnet_conv_shapes(config)))


def resnet_train_flops_per_image(config: dict) -> float:
    """3 x forward x 2 operations a multiply-add; batch-norm, ReLU, pooling
    and the loss are not matmul work and are left out (about 1 %)."""
    return 3.0 * 2.0 * resnet_forward_macs_per_image(config)


def flash_flops_bytes(batch: int, heads: int, seq: int, head_dim: int,
                      causal: bool = True, itemsize: int = 2):
    """(operations, bytes) one forward AND one backward attention call need.

    Forward: S = QK^T and O = PV, 2 products of 2*seq*seq*head_dim each per
    head. Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q, 4
    products; the scores a flash backward recomputes are recomputation and
    not counted. A causal mask halves every product. Bytes: the forward reads
    Q, K, V and writes O; the backward reads Q, K, V, O, dO and writes dQ, dK,
    dV: 12 tensors of batch*heads*seq*head_dim elements (the log-sum-exp rows
    are 1/head_dim of one tensor and left out)."""
    product = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        product *= 0.5
    flops = 6.0 * product
    nbytes = 12.0 * batch * heads * seq * head_dim * itemsize
    return flops, nbytes
