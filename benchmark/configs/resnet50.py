"""``resnet50``: ResNet-50 v1.5 trained on batch statistics, and its
plain reference.

The system under test is ``horovod_tpu.models.resnet.ResNet`` with the
published 7x7/2 stem.  BatchNorm computes batch statistics
(``train=True``); the running averages it returns are dropped, because
``DistributedTrainStep`` cannot carry them (``resnet50.json``,
``assumed``).  The reference below is the same network written out in
float32 ``jax.numpy`` / ``lax`` on the same parameter tree.  Data: a
seeded low-resolution pattern a class under uint8 noise, the class as
label, shipped as uint8 and scaled on the device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from benchmark import flops
from benchmark.configs import Built, ParityCase

BN_EPSILON = 1e-5


def _model(config: dict, stage_sizes):
    from horovod_tpu.models.resnet import ResNet

    return ResNet(stage_sizes=list(stage_sizes),
                  num_classes=config["num_classes"],
                  num_filters=config["num_filters"],
                  dtype=jnp.dtype(config["compute_dtype"]),
                  space_to_depth=False)


def _scaled(image):
    return (image.astype(jnp.float32) - 127.5) * (1.0 / 127.5)


def _loss_fn(model):
    def loss_fn(params, batch):
        logits, _running = model.apply(
            {"params": params}, _scaled(batch["image"]), train=True,
            mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]).mean()
    return loss_fn


def _init(model, config: dict):
    size = config["image_size"]
    return lambda key: model.init(
        key, jnp.zeros((1, size, size, 3), jnp.float32),
        train=False)["params"]


def _sampler(config: dict, seed: int):
    size, classes = config["image_size"], config["num_classes"]
    cells = config["pattern_cells"]
    if size % cells or (size * size * 3) % 8:
        raise ValueError(f"pattern_cells {cells} must divide image_size "
                         f"{size}, and an image must be whole 8-byte words")
    patterns = np.random.default_rng(seed).integers(
        0, 128, (classes, cells, cells, 3), dtype=np.uint8)

    def sample(rng, n: int) -> dict:
        # one 150 KB-an-image buffer, filled and finished in place: every
        # further copy of it costs the set-up seconds
        label = rng.integers(0, classes, n, dtype=np.int32)
        k = size // cells
        words = rng.integers(0, 2 ** 64, n * size * size * 3 // 8,
                             dtype=np.uint64)
        image = words.view(np.uint8).reshape(n, cells, k, cells, k, 3)
        image &= np.uint8(0x7F)
        image += patterns[label][:, :, None, :, None, :]   # < 128 each
        return {"image": image.reshape(n, size, size, 3), "label": label}
    return sample


def build(config: dict, job: dict, chips: int, seed: int) -> Built:
    model = _model(config, config["stage_sizes"])
    opt = config["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"resnet50 trains with sgd, got {opt}")
    return Built(
        init=_init(model, config), loss_fn=_loss_fn(model),
        optimizer=optax.sgd(opt["learning_rate_per_chip"] * chips,
                            momentum=opt["momentum"]),
        sample=_sampler(config, seed),
        units_per_sample=1,
        flops_per_unit=flops.resnet_flops_per_image(
            config["stage_sizes"], config["image_size"],
            config["num_filters"], config["num_classes"]))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _conv(x, kernel, stride: int, padding):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + BN_EPSILON) * p["scale"] + p["bias"]


def reference_loss(params, batch, *, stage_sizes):
    """Cross-entropy of the bottleneck ResNet v1.5 on batch statistics,
    in float32, written out."""
    x = _scaled(batch["image"])
    x = _conv(x, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(_batch_norm(x, params["bn_init"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    block = 0
    for i, count in enumerate(stage_sizes):
        for j in range(count):
            p = params[f"BottleneckBlock_{block}"]
            block += 1
            stride = 2 if i > 0 and j == 0 else 1
            y = _conv(x, p["Conv_0"]["kernel"], 1, "SAME")
            y = jax.nn.relu(_batch_norm(y, p["BatchNorm_0"]))
            y = _conv(y, p["Conv_1"]["kernel"], stride, "SAME")
            y = jax.nn.relu(_batch_norm(y, p["BatchNorm_1"]))
            y = _conv(y, p["Conv_2"]["kernel"], 1, "SAME")
            y = _batch_norm(y, p["BatchNorm_2"])
            if "conv_proj" in p:
                x = _conv(x, p["conv_proj"]["kernel"], stride, "SAME")
                x = _batch_norm(x, p["norm_proj"])
            x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(1, 2))
    with jax.default_matmul_precision("highest"):
        logits = x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, batch["label"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def parity_case(config: dict, job: dict, chips: int, seed: int) -> ParityCase:
    cut = config["parity"]
    model = _model(config, cut["stage_sizes"])
    init = _init(model, config)

    def init_with_unit_scales(key):
        # a zero-initialised closing scale would switch its block's
        # convolutions out of the gradient; the check wants them in
        return jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.ones_like(x)
            if path[-1].key == "scale" else x, init(key))

    def reference(params, batch):
        return reference_loss(params, batch,
                              stage_sizes=cut["stage_sizes"])

    return ParityCase(
        init=init_with_unit_scales, loss_fn=_loss_fn(model),
        reference_loss=reference, sample=_sampler(config, seed),
        rows_per_chip=cut["rows_per_chip"],
        loss_rtol=cut["loss_rtol"],
        grad_norm_rtol=cut["grad_norm_rtol"],
        grad_rel_l2=cut["grad_rel_l2"])
