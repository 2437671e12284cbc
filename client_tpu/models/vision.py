"""Image-classification flagship models: ResNet-50 and DenseNet-121.

These are the serving-side counterparts of the models the reference's image
clients drive (/root/reference/src/c++/examples/image_client.cc:26-120
preprocesses for "resnet"-style models; BASELINE.json configs 3-4 name
`resnet50` and `densenet_onnx`). The reference repo carries no model code —
models live behind the server boundary — so these are TPU-first designs, not
translations:

- NHWC layout end to end (TPU conv layout; the MXU consumes HWIO kernels),
- bfloat16 weights and activations, float32 batch-norm statistics and final
  logits,
- inference-mode batch norm folded to a scale/bias affine (no running-stat
  bookkeeping inside the jitted step),
- one pure ``apply`` over a params pytree, jitted once per batch bucket by
  the engine (engine/model.py).

Weights are deterministic random (He-style fans) — the reference ships no
weights either (models/ has config.pbtxt only); benchmark realism comes from
architecture/FLOPs, not weight values.
"""

from __future__ import annotations

import numpy as np

from client_tpu.engine.config import (
    DynamicBatchingConfig,
    ModelConfig,
    TensorConfig,
)
from client_tpu.engine.model import ModelBackend
from client_tpu.models import register_model


def _conv_init(key, kh, kw, cin, cout, dtype):
    import jax

    fan_in = kh * kw * cin
    std = np.sqrt(2.0 / fan_in)
    return (jax.random.normal(key, (kh, kw, cin, cout)) * std).astype(dtype)


def _dense_init(key, cin, cout, dtype):
    import jax

    std = np.sqrt(1.0 / cin)
    return (jax.random.normal(key, (cin, cout)) * std).astype(dtype)


def _conv(x, w, stride=1, padding="SAME", feature_group_count=1):
    import jax

    return jax.lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=feature_group_count,
    )


def _bn_params(key, c, dtype):
    """Inference-mode batch norm folded to affine: y = x*scale + bias."""
    import jax

    scale = 1.0 + 0.1 * jax.random.normal(key, (c,))
    return {"scale": scale.astype(dtype), "bias": np.zeros((c,), dtype)}


def _bn(x, p):
    return x * p["scale"] + p["bias"]


def _max_pool(x, window, stride, padding="SAME"):
    import jax

    return jax.lax.reduce_window(
        x, -np.inf, jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1), padding)


def _avg_pool_global(x):
    import jax.numpy as jnp

    return jnp.mean(x.astype(jnp.float32), axis=(1, 2))


# ---------------------------------------------------------------------------
# ResNet-50
# ---------------------------------------------------------------------------

_RESNET50_STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))
_EXPANSION = 4


class ResNet50Backend(ModelBackend):
    """ResNet-50 classifier: FP32 NHWC [224,224,3] -> FP32 [1000] logits."""

    def __init__(self, name: str = "resnet50", num_classes: int = 1000,
                 image_size: int = 224, stages=_RESNET50_STAGES,
                 max_batch_size: int = 32):
        self._num_classes = num_classes
        self._stages = stages
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=max_batch_size,
            input=[TensorConfig("INPUT", "FP32", [image_size, image_size, 3])],
            output=[TensorConfig("OUTPUT", "FP32", [num_classes])],
            dynamic_batching=DynamicBatchingConfig(
                preferred_batch_size=[max(1, max_batch_size // 2),
                                      max_batch_size],
                max_queue_delay_microseconds=500,
            ),
            instance_count=2,
        )

    def _init_params(self):
        import jax
        import jax.numpy as jnp

        dt = jnp.bfloat16
        key = jax.random.PRNGKey(50)

        def nk():
            nonlocal key
            key, sub = jax.random.split(key)
            return sub

        params = {
            "stem": {"w": _conv_init(nk(), 7, 7, 3, 64, dt),
                     "bn": _bn_params(nk(), 64, dt)},
            "stages": [],
        }
        cin = 64
        for n_blocks, width in self._stages:
            blocks = []
            for b in range(n_blocks):
                cout = width * _EXPANSION
                blk = {
                    "w1": _conv_init(nk(), 1, 1, cin, width, dt),
                    "bn1": _bn_params(nk(), width, dt),
                    "w2": _conv_init(nk(), 3, 3, width, width, dt),
                    "bn2": _bn_params(nk(), width, dt),
                    "w3": _conv_init(nk(), 1, 1, width, cout, dt),
                    "bn3": _bn_params(nk(), cout, dt),
                }
                if b == 0:
                    blk["wproj"] = _conv_init(nk(), 1, 1, cin, cout, dt)
                    blk["bnproj"] = _bn_params(nk(), cout, dt)
                blocks.append(blk)
                cin = cout
            params["stages"].append(blocks)
        params["fc"] = {
            "w": _dense_init(nk(), cin, self._num_classes, dt),
            "b": np.zeros((self._num_classes,), np.float32),
        }
        return params

    def make_apply_params(self):
        import jax

        def bottleneck(x, blk, stride):
            y = jax.nn.relu(_bn(_conv(x, blk["w1"]), blk["bn1"]))
            y = jax.nn.relu(_bn(_conv(y, blk["w2"], stride=stride), blk["bn2"]))
            y = _bn(_conv(y, blk["w3"]), blk["bn3"])
            if "wproj" in blk:
                x = _bn(_conv(x, blk["wproj"], stride=stride), blk["bnproj"])
            return jax.nn.relu(x + y)

        def apply(params, inputs):
            import jax
            import jax.numpy as jnp

            x = inputs["INPUT"].astype(jnp.bfloat16)
            x = jax.nn.relu(_bn(_conv(x, params["stem"]["w"], stride=2),
                                params["stem"]["bn"]))
            x = _max_pool(x, 3, 2)
            for si, blocks in enumerate(params["stages"]):
                for bi, blk in enumerate(blocks):
                    stride = 2 if (si > 0 and bi == 0) else 1
                    x = bottleneck(x, blk, stride)
            pooled = _avg_pool_global(x)  # fp32 [B, C]
            fc = params["fc"]
            logits = pooled @ fc["w"].astype(jnp.float32) + fc["b"]
            return {"OUTPUT": logits}

        return apply, jax.device_put(self.load_or_init_params(self._init_params))


# ---------------------------------------------------------------------------
# DenseNet-121
# ---------------------------------------------------------------------------

_DENSENET121_BLOCKS = (6, 12, 24, 16)


class DenseNet121Backend(ModelBackend):
    """DenseNet-121 classifier (`densenet_onnx` parity name lives in the
    registry): FP32 NHWC [224,224,3] -> FP32 [1000] logits."""

    def __init__(self, name: str = "densenet_onnx", num_classes: int = 1000,
                 image_size: int = 224, blocks=_DENSENET121_BLOCKS,
                 growth: int = 32, max_batch_size: int = 16):
        self._num_classes = num_classes
        self._blocks = blocks
        self._growth = growth
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=max_batch_size,
            input=[TensorConfig("INPUT", "FP32", [image_size, image_size, 3])],
            output=[TensorConfig("OUTPUT", "FP32", [num_classes])],
            dynamic_batching=DynamicBatchingConfig(
                preferred_batch_size=[max(1, max_batch_size // 2),
                                      max_batch_size],
                max_queue_delay_microseconds=500,
            ),
        )

    def _init_params(self):
        import jax
        import jax.numpy as jnp

        dt = jnp.bfloat16
        g = self._growth
        key = jax.random.PRNGKey(121)

        def nk():
            nonlocal key
            key, sub = jax.random.split(key)
            return sub

        params = {
            "stem": {"w": _conv_init(nk(), 7, 7, 3, 2 * g, dt),
                     "bn": _bn_params(nk(), 2 * g, dt)},
            "blocks": [],
            "transitions": [],
        }
        c = 2 * g
        for i, n_layers in enumerate(self._blocks):
            layers = []
            for _ in range(n_layers):
                layers.append({
                    "bn1": _bn_params(nk(), c, dt),
                    "w1": _conv_init(nk(), 1, 1, c, 4 * g, dt),
                    "bn2": _bn_params(nk(), 4 * g, dt),
                    "w2": _conv_init(nk(), 3, 3, 4 * g, g, dt),
                })
                c += g
            params["blocks"].append(layers)
            if i < len(self._blocks) - 1:
                cout = c // 2
                params["transitions"].append({
                    "bn": _bn_params(nk(), c, dt),
                    "w": _conv_init(nk(), 1, 1, c, cout, dt),
                })
                c = cout
        params["final_bn"] = _bn_params(nk(), c, dt)
        params["fc"] = {
            "w": _dense_init(nk(), c, self._num_classes, dt),
            "b": np.zeros((self._num_classes,), np.float32),
        }
        return params

    def make_apply_params(self):
        import jax

        def dense_layer(x, lyr):
            y = _conv(jax.nn.relu(_bn(x, lyr["bn1"])), lyr["w1"])
            y = _conv(jax.nn.relu(_bn(y, lyr["bn2"])), lyr["w2"])
            return y

        def apply(params, inputs):
            import jax
            import jax.numpy as jnp

            x = inputs["INPUT"].astype(jnp.bfloat16)
            x = jax.nn.relu(_bn(_conv(x, params["stem"]["w"], stride=2),
                                params["stem"]["bn"]))
            x = _max_pool(x, 3, 2)
            for i, layers in enumerate(params["blocks"]):
                for lyr in layers:
                    y = dense_layer(x, lyr)
                    x = jnp.concatenate([x, y], axis=-1)
                if i < len(params["blocks"]) - 1:
                    tr = params["transitions"][i]
                    x = _conv(jax.nn.relu(_bn(x, tr["bn"])), tr["w"])
                    x = _avg_pool2(x)
            x = jax.nn.relu(_bn(x, params["final_bn"]))
            pooled = _avg_pool_global(x)
            fc = params["fc"]
            logits = pooled @ fc["w"].astype(jnp.float32) + fc["b"]
            return {"OUTPUT": logits}

        return apply, jax.device_put(self.load_or_init_params(self._init_params))


def _avg_pool2(x):
    import jax

    summed = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    return summed * 0.25


register_model("resnet50")(ResNet50Backend)
register_model("densenet_onnx")(DenseNet121Backend)
