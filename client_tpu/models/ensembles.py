"""Ensemble pipelines and their composing pre/post-process models.

BASELINE.json config 5 names the flagship pipeline: preprocess → BERT-base →
postprocess with string I/O, served like the reference serves ensembles
(composing steps declared via input_map/output_map, executed by the engine's
EnsembleScheduler with per-composing-model statistics — the reference's perf
harness rolls these up in inference_profiler.cc:910-960).

Composing host-side models (jittable=False — BYTES object arrays cannot
enter XLA; this mirrors Triton's Python/DALI preprocess backends):

- ``bert_preprocess``   BYTES text [1] -> input_ids/attention_mask INT32[S]
  (deterministic hash wordpiece stand-in — no vocab files ship with the
  reference either)
- ``bert_postprocess``  logits FP32[num_labels] -> BYTES label + FP32 score
- ``image_preprocess``  UINT8 HWC (any size) -> FP32 [224,224,3] resized and
  normalized (the reference's image_client does this client-side with
  OpenCV, image_client.cc:26-120; ensemble_image_client pushes it into an
  ensemble, which is what this models)

Ensembles:

- ``ensemble_bert``  TEXT -> LABEL, SCORE        (preprocess→bert_base→post)
- ``ensemble_image`` RAW_IMAGE -> CLASS_LOGITS   (image_preprocess→resnet50)
"""

from __future__ import annotations

import numpy as np

from client_tpu.engine.config import EnsembleStep, ModelConfig, TensorConfig
from client_tpu.engine.model import ModelBackend
from client_tpu.models import register_model
from client_tpu.models.bert import BertBackend

SEQ_LEN = 128
CLS_ID = 101
SEP_ID = 102


def _hash_token(tok: bytes) -> int:
    """Stable token-id hash into the BERT vocab range (1000..30521)."""
    h = 2166136261
    for c in tok:
        h = ((h ^ c) * 16777619) & 0xFFFFFFFF
    return 1000 + h % (30522 - 1000)


class BertPreprocessBackend(ModelBackend):
    jittable = False

    def __init__(self, name: str = "bert_preprocess", seq_len: int = SEQ_LEN):
        self.seq_len = seq_len
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=8,
            input=[TensorConfig("TEXT", "BYTES", [1])],
            output=[
                TensorConfig("input_ids", "INT32", [seq_len]),
                TensorConfig("attention_mask", "INT32", [seq_len]),
            ],
        )

    def make_apply(self):
        seq_len = self.seq_len

        def apply(inputs):
            texts = inputs["TEXT"]
            batch = texts.shape[0]
            ids = np.zeros((batch, seq_len), np.int32)
            mask = np.zeros((batch, seq_len), np.int32)
            for i in range(batch):
                raw = texts[i, 0]
                if isinstance(raw, str):
                    raw = raw.encode()
                toks = [_hash_token(t) for t in bytes(raw).lower().split()]
                toks = [CLS_ID] + toks[: seq_len - 2] + [SEP_ID]
                ids[i, : len(toks)] = toks
                mask[i, : len(toks)] = 1
            return {"input_ids": ids, "attention_mask": mask}

        return apply


class BertPostprocessBackend(ModelBackend):
    jittable = False

    LABELS = (b"negative", b"positive")

    def __init__(self, name: str = "bert_postprocess", num_labels: int = 2):
        self.num_labels = num_labels
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=8,
            input=[TensorConfig("logits", "FP32", [num_labels])],
            output=[
                TensorConfig("LABEL", "BYTES", [1]),
                TensorConfig("SCORE", "FP32", [1]),
            ],
        )

    def make_apply(self):
        def apply(inputs):
            logits = np.asarray(inputs["logits"], np.float32)
            exp = np.exp(logits - logits.max(axis=-1, keepdims=True))
            probs = exp / exp.sum(axis=-1, keepdims=True)
            best = probs.argmax(axis=-1)
            labels = np.array(
                [[self.LABELS[min(b, len(self.LABELS) - 1)]] for b in best],
                dtype=np.object_)
            scores = probs.max(axis=-1, keepdims=True).astype(np.float32)
            return {"LABEL": labels, "SCORE": scores}

        return apply


class ImagePreprocessBackend(ModelBackend):
    """UINT8 [H,W,3] (any size) -> FP32 [224,224,3], mean/std normalized."""

    jittable = False

    MEAN = np.array([123.675, 116.28, 103.53], np.float32)
    STD = np.array([58.395, 57.12, 57.375], np.float32)

    def __init__(self, name: str = "image_preprocess", size: int = 224):
        self.size = size
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=8,
            input=[TensorConfig("RAW_IMAGE", "UINT8", [-1, -1, 3])],
            output=[TensorConfig("IMAGE", "FP32", [size, size, 3])],
        )

    def make_apply(self):
        size = self.size

        def apply(inputs):
            imgs = inputs["RAW_IMAGE"]
            batch = imgs.shape[0]
            out = np.empty((batch, size, size, 3), np.float32)
            for i in range(batch):
                img = imgs[i]
                h, w = img.shape[0], img.shape[1]
                # nearest-neighbor resize (host-side; no OpenCV in-tree)
                ys = (np.arange(size) * h // size).clip(0, h - 1)
                xs = (np.arange(size) * w // size).clip(0, w - 1)
                resized = img[ys][:, xs].astype(np.float32)
                out[i] = (resized - self.MEAN) / self.STD
            return {"IMAGE": out}

        return apply


class EnsembleBertBackend(ModelBackend):
    """preprocess → bert_base → postprocess, string I/O end to end."""

    def __init__(self, name: str = "ensemble_bert"):
        self.config = ModelConfig(
            name=name,
            platform="ensemble",
            max_batch_size=8,
            input=[TensorConfig("TEXT", "BYTES", [1])],
            output=[
                TensorConfig("LABEL", "BYTES", [1]),
                TensorConfig("SCORE", "FP32", [1]),
            ],
            ensemble_scheduling=[
                EnsembleStep(
                    model_name="bert_preprocess",
                    input_map={"TEXT": "TEXT"},
                    output_map={"input_ids": "_ids",
                                "attention_mask": "_mask"},
                ),
                EnsembleStep(
                    model_name="bert_base",
                    input_map={"input_ids": "_ids",
                               "attention_mask": "_mask"},
                    output_map={"logits": "_logits"},
                ),
                EnsembleStep(
                    model_name="bert_postprocess",
                    input_map={"logits": "_logits"},
                    output_map={"LABEL": "LABEL", "SCORE": "SCORE"},
                ),
            ],
        )


class EnsembleImageBackend(ModelBackend):
    """image_preprocess → resnet50 (the reference's ensemble_image_client
    pipeline shape, /root/reference/src/c++/examples/ensemble_image_client.cc)."""

    def __init__(self, name: str = "ensemble_image"):
        self.config = ModelConfig(
            name=name,
            platform="ensemble",
            max_batch_size=8,
            input=[TensorConfig("RAW_IMAGE", "UINT8", [-1, -1, 3])],
            output=[TensorConfig("CLASS_LOGITS", "FP32", [1000])],
            ensemble_scheduling=[
                EnsembleStep(
                    model_name="image_preprocess",
                    input_map={"RAW_IMAGE": "RAW_IMAGE"},
                    output_map={"IMAGE": "_image"},
                ),
                EnsembleStep(
                    model_name="resnet50",
                    input_map={"INPUT": "_image"},
                    output_map={"OUTPUT": "CLASS_LOGITS"},
                ),
            ],
        )


register_model("bert_preprocess")(BertPreprocessBackend)
register_model("bert_postprocess")(BertPostprocessBackend)
register_model("image_preprocess")(ImagePreprocessBackend)
register_model("ensemble_bert")(EnsembleBertBackend)
register_model("ensemble_image")(EnsembleImageBackend)

# keep an explicit reference so linters see BertBackend as used (the ensemble
# depends on `bert_base` being registered by client_tpu.models.bert)
_ = BertBackend
