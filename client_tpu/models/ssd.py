"""SSD-MobileNet-v2 COCO detector (`ssd_mobilenet_v2_coco_quantized`).

Wire-level parity with the reference's in-tree model config
(/root/reference/models/ssd_mobilenet_v2_coco_quantized/config.pbtxt:1-36):
UINT8 NHWC [300,300,3] input named ``normalized_input_image_tensor``; four
FP32 outputs named ``TFLite_Detection_PostProcess[:1|:2|:3]`` with dims
[1,10,4] boxes, [1,10] classes, [1,10] scores, [1] count; max_batch_size 1.

The implementation is TPU-first, not TFLite: the backbone is a MobileNetV2
inverted-residual stack (depthwise separable convs in bfloat16 on the MXU),
SSD box/class heads run over six feature-map scales, and the detection
postprocess (box decode + top-K NMS) runs **in-graph** with static shapes —
``lax.fori_loop`` greedy NMS over the top-scoring candidates instead of the
reference's CPU TFLite_Detection_PostProcess op. "quantized" parity: the
wire input stays UINT8 (dequantized on device); matmul precision is bf16.

A batched variant ``ssd_mobilenet_v2_tpu`` (max_batch_size 16, dynamic
batching) is also registered — that's the BASELINE.json north-star bench
target, where batch>1 keeps the MXU fed.
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
from client_tpu.models.vision import _bn, _bn_params, _conv, _conv_init

NUM_CLASSES = 91          # COCO label map (91 ids incl. background gaps)
MAX_DETECTIONS = 10       # reference config output dims [1, 10, 4]
IOU_THRESHOLD = 0.5
SCORE_THRESHOLD = 0.05

# MobileNetV2 inverted-residual spec: (expansion, out_channels, n, stride)
_MBV2_SPEC = (
    (1, 16, 1, 1),
    (6, 24, 2, 2),
    (6, 32, 3, 2),
    (6, 64, 4, 2),
    (6, 96, 3, 1),
    (6, 160, 3, 2),
    (6, 320, 1, 1),
)

# SSD feature-map sizes for a 300x300 input and anchors per cell.
_FEATURE_MAPS = ((19, 3), (10, 6), (5, 6), (3, 6), (2, 6), (1, 6))
_SCALES = (0.2, 0.35, 0.5, 0.65, 0.8, 0.95)


def _mbv2_blocks():
    """Flattened per-block structure of ``_MBV2_SPEC``:
    (cin, cout, expansion, stride, residual) — the single source both the
    weight init and the traced apply iterate, so the params list and the
    static stride/residual flags can't drift out of lockstep."""
    out = []
    cin = 32
    for expansion, cout, n, stride in _MBV2_SPEC:
        for i in range(n):
            out.append((cin, cout, expansion,
                        stride if i == 0 else 1,
                        (i > 0 or stride == 1) and cin == cout))
            cin = cout
    return out


def _make_anchors():
    """Static [N,4] anchor boxes (cy, cx, h, w) in normalized coords."""
    all_anchors = []
    for (fm, n_anchors), scale in zip(_FEATURE_MAPS, _SCALES):
        ratios = (1.0, 2.0, 0.5, 3.0, 1.0 / 3.0, 1.0)[:n_anchors]
        for y in range(fm):
            for x in range(fm):
                cy, cx = (y + 0.5) / fm, (x + 0.5) / fm
                for i, r in enumerate(ratios):
                    s = scale * (1.25 if (i == n_anchors - 1 and n_anchors > 1)
                                 else 1.0)
                    all_anchors.append(
                        [cy, cx, s / np.sqrt(r), s * np.sqrt(r)])
    return np.asarray(all_anchors, np.float32)


class SsdMobileNetV2Backend(ModelBackend):
    def __init__(self, name: str = "ssd_mobilenet_v2_coco_quantized",
                 max_batch_size: int = 1, image_size: int = 300):
        self._image_size = image_size
        batched = max_batch_size > 1
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=max_batch_size,
            input=[TensorConfig("normalized_input_image_tensor", "UINT8",
                                [image_size, image_size, 3])],
            output=[
                TensorConfig("TFLite_Detection_PostProcess", "FP32",
                             [1, MAX_DETECTIONS, 4]),
                TensorConfig("TFLite_Detection_PostProcess:1", "FP32",
                             [1, MAX_DETECTIONS]),
                TensorConfig("TFLite_Detection_PostProcess:2", "FP32",
                             [1, MAX_DETECTIONS]),
                TensorConfig("TFLite_Detection_PostProcess:3", "FP32", [1]),
            ],
            dynamic_batching=DynamicBatchingConfig(
                preferred_batch_size=[max_batch_size],
                max_queue_delay_microseconds=300,
            ) if batched else None,
            instance_count=2,
        )
        self._anchors = _make_anchors()

    def _init_params(self):
        import jax
        import jax.numpy as jnp

        dt = jnp.bfloat16
        key = jax.random.PRNGKey(300)

        def nk():
            nonlocal key
            key, sub = jax.random.split(key)
            return sub

        params = {"stem": {"w": _conv_init(nk(), 3, 3, 3, 32, dt),
                           "bn": _bn_params(nk(), 32, dt)},
                  "blocks": [], "heads": [], "extras": []}
        cin = 32
        for cin, cout, expansion, _stride, _residual in _mbv2_blocks():
            mid = cin * expansion
            blk = {
                "bn1": _bn_params(nk(), mid, dt),
                "wd": _conv_init(nk(), 3, 3, 1, mid, dt),  # depthwise HWI(1)O
                "bn2": _bn_params(nk(), mid, dt),
                "wp": _conv_init(nk(), 1, 1, mid, cout, dt),
                "bn3": _bn_params(nk(), cout, dt),
            }
            if expansion != 1:
                blk["we"] = _conv_init(nk(), 1, 1, cin, mid, dt)
            params["blocks"].append(blk)
        cin = _mbv2_blocks()[-1][1]
        # extra feature layers down to 1x1 (channels cin -> 256 each)
        for _ in range(len(_FEATURE_MAPS) - 2):
            params["extras"].append({
                "w1": _conv_init(nk(), 1, 1, cin, 128, dt),
                "bn1": _bn_params(nk(), 128, dt),
                "w2": _conv_init(nk(), 3, 3, 128, 256, dt),
                "bn2": _bn_params(nk(), 256, dt),
            })
            cin = 256
        # heads: one box + one class conv per feature map
        head_cins = [576, 320] + [256] * (len(_FEATURE_MAPS) - 2)
        for (fm, n_anchors), hc in zip(_FEATURE_MAPS, head_cins):
            params["heads"].append({
                "box": _conv_init(nk(), 3, 3, hc, n_anchors * 4, dt),
                "cls": _conv_init(nk(), 3, 3, hc, n_anchors * NUM_CLASSES, dt),
            })
        return params

    def make_apply_params(self):
        import jax

        anchors = self._anchors
        n_anchors_total = anchors.shape[0]
        # Per-block static structure (conv strides, residual flags) stays
        # host-side: it parameterizes the traced program and must not ride in
        # the params argument, where leaves become traced arrays.
        statics = [(stride, residual)
                   for _cin, _cout, _exp, stride, residual in _mbv2_blocks()]

        def backbone_feats(params, x):
            feats = []
            y = jax.nn.relu6(_bn(_conv(x, params["stem"]["w"], stride=2),
                                 params["stem"]["bn"]))
            for bi, (blk, (stride, residual)) in enumerate(
                    zip(params["blocks"], statics)):
                inp = y
                if "we" in blk:
                    expanded = jax.nn.relu6(
                        _bn(_conv(y, blk["we"]), blk["bn1"]))
                else:
                    expanded = y
                mid = expanded.shape[-1]
                y = jax.nn.relu6(_bn(
                    _conv(expanded, blk["wd"], stride=stride,
                          feature_group_count=mid), blk["bn2"]))
                y = _bn(_conv(y, blk["wp"]), blk["bn3"])
                if residual:
                    y = y + inp
                if bi == 13 and "we" in blk:
                    # 19x19 tap: expansion conv of the first 160-stage block
                    feats.append(expanded)
            feats.append(y)  # 10x10, 320 channels
            for ex in params["extras"]:
                y = jax.nn.relu6(_bn(_conv(y, ex["w1"]), ex["bn1"]))
                y = jax.nn.relu6(_bn(_conv(y, ex["w2"], stride=2),
                                     ex["bn2"]))
                feats.append(y)
            return feats

        def decode_and_nms(boxes_enc, scores_all):
            """boxes_enc [N,4] fp32, scores_all [N,C] fp32 -> top-10 dets."""
            import jax.numpy as jnp

            cy = anchors[:, 0] + 0.1 * boxes_enc[:, 0] * anchors[:, 2]
            cx = anchors[:, 1] + 0.1 * boxes_enc[:, 1] * anchors[:, 3]
            h = anchors[:, 2] * jnp.exp(0.2 * boxes_enc[:, 2])
            w = anchors[:, 3] * jnp.exp(0.2 * boxes_enc[:, 3])
            ymin, xmin = cy - h / 2, cx - w / 2
            ymax, xmax = cy + h / 2, cx + w / 2
            boxes = jnp.stack([ymin, xmin, ymax, xmax], axis=1)

            cls_scores = scores_all[:, 1:]  # drop background column 0
            best_cls = jnp.argmax(cls_scores, axis=1).astype(jnp.float32)
            best_score = jnp.max(cls_scores, axis=1)
            best_score = jnp.where(best_score >= SCORE_THRESHOLD,
                                   best_score, 0.0)

            area = jnp.maximum(ymax - ymin, 0) * jnp.maximum(xmax - xmin, 0)

            def iou_with(box):
                iy1 = jnp.maximum(boxes[:, 0], box[0])
                ix1 = jnp.maximum(boxes[:, 1], box[1])
                iy2 = jnp.minimum(boxes[:, 2], box[2])
                ix2 = jnp.minimum(boxes[:, 3], box[3])
                inter = jnp.maximum(iy2 - iy1, 0) * jnp.maximum(ix2 - ix1, 0)
                box_area = jnp.maximum(box[2] - box[0], 0) * \
                    jnp.maximum(box[3] - box[1], 0)
                return inter / jnp.maximum(area + box_area - inter, 1e-9)

            def body(i, state):
                scores, out_boxes, out_cls, out_scores = state
                j = jnp.argmax(scores)
                s = scores[j]
                box = boxes[j]
                keep = s > 0.0
                out_boxes = out_boxes.at[i].set(jnp.where(keep, box, 0.0))
                out_cls = out_cls.at[i].set(jnp.where(keep, best_cls[j], 0.0))
                out_scores = out_scores.at[i].set(jnp.where(keep, s, 0.0))
                # suppress overlapping candidates (greedy class-agnostic NMS)
                suppress = iou_with(box) > IOU_THRESHOLD
                scores = jnp.where(suppress & keep, 0.0, scores)
                scores = scores.at[j].set(0.0)
                return scores, out_boxes, out_cls, out_scores

            init = (best_score,
                    jnp.zeros((MAX_DETECTIONS, 4), jnp.float32),
                    jnp.zeros((MAX_DETECTIONS,), jnp.float32),
                    jnp.zeros((MAX_DETECTIONS,), jnp.float32))
            _, out_boxes, out_cls, out_scores = jax.lax.fori_loop(
                0, MAX_DETECTIONS, body, init)
            count = jnp.sum((out_scores > 0).astype(jnp.float32))
            return out_boxes, out_cls, out_scores, count

        def apply(params, inputs):
            import jax.numpy as jnp

            # Engine always supplies the batch dim when max_batch_size > 0
            # (model.py validate_inputs); per-sample output dims are
            # [1,10,4] / [1,10] / [1] per the reference config, so a leading
            # singleton is inserted per sample below.
            img = inputs["normalized_input_image_tensor"]
            x = (img.astype(jnp.bfloat16) - 127.5) / 127.5
            feats = backbone_feats(params, x)

            b = x.shape[0]
            box_parts, cls_parts = [], []
            for feat, head in zip(feats, params["heads"]):
                raw_box = _conv(feat, head["box"]).astype(jnp.float32)
                raw_cls = _conv(feat, head["cls"]).astype(jnp.float32)
                box_parts.append(raw_box.reshape(b, -1, 4))
                cls_parts.append(raw_cls.reshape(b, -1, NUM_CLASSES))
            boxes_enc = jnp.concatenate(box_parts, axis=1)
            scores_all = jax.nn.sigmoid(jnp.concatenate(cls_parts, axis=1))
            assert boxes_enc.shape[1] == n_anchors_total, \
                (boxes_enc.shape, n_anchors_total)

            out_b, out_c, out_s, count = jax.vmap(decode_and_nms)(
                boxes_enc, scores_all)

            return {
                "TFLite_Detection_PostProcess": out_b[:, None],
                "TFLite_Detection_PostProcess:1": out_c[:, None],
                "TFLite_Detection_PostProcess:2": out_s[:, None],
                "TFLite_Detection_PostProcess:3": count[:, None],
            }

        return apply, jax.device_put(self.load_or_init_params(self._init_params))


class SsdMobileNetV2TpuBackend(SsdMobileNetV2Backend):
    """Batched TPU-throughput variant — BASELINE.json north-star bench model."""

    def __init__(self):
        super().__init__(name="ssd_mobilenet_v2_tpu", max_batch_size=16)


register_model("ssd_mobilenet_v2_coco_quantized")(SsdMobileNetV2Backend)
register_model("ssd_mobilenet_v2_tpu")(SsdMobileNetV2TpuBackend)
