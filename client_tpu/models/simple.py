"""The `simple*` conformance-model family.

Behavioral oracles for the whole client stack, matching the models the
reference's examples assert against (add/sub INT32[16]:
/root/reference/src/c++/examples/simple_grpc_infer_client.cc:337; string,
identity, sequence and repeat variants exercised by the simple_* example
pairs, SURVEY.md §2.7).
"""

from __future__ import annotations

from typing import Any, Iterator

import numpy as np

from client_tpu.engine.config import (
    DynamicBatchingConfig,
    ModelConfig,
    SequenceBatchingConfig,
    TensorConfig,
)
from client_tpu.engine.model import ModelBackend
from client_tpu.models import register_model


class AddSubBackend(ModelBackend):
    """INT32[16] -> OUTPUT0=sum, OUTPUT1=diff. The canonical `simple` model."""

    def __init__(self, name: str = "simple", n: int = 16,
                 max_batch_size: int = 64, datatype: str = "INT32"):
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=max_batch_size,
            input=[
                TensorConfig("INPUT0", datatype, [n]),
                TensorConfig("INPUT1", datatype, [n]),
            ],
            output=[
                TensorConfig("OUTPUT0", datatype, [n]),
                TensorConfig("OUTPUT1", datatype, [n]),
            ],
            dynamic_batching=DynamicBatchingConfig(
                preferred_batch_size=sorted(
                    {min(8, max_batch_size), max_batch_size}),
                max_queue_delay_microseconds=100,
            ),
            # A deep batching ceiling matters more than compute here: each
            # device round trip has a fixed dispatch cost, so throughput
            # scales with how many requests ride one dispatch.  The ladder
            # and the instance count below were sized when that round trip
            # was tens of ms — a transport that is gone; re-decide on the
            # chip (ROADMAP A).  Small bucket set (clamped to the configured
            # ceiling) keeps warmup compiles cheap.
            batch_buckets=sorted(
                {b for b in (1, 8, 64) if b <= max_batch_size}
                | {max_batch_size}),
            # Several executor instances keep multiple batches in flight so
            # device round-trips overlap (the device transport pipelines
            # concurrent dispatch+fetch; serialized batches leave it idle).
            instance_count=4,
        )

    def make_apply(self):
        def apply(inputs):
            a, b = inputs["INPUT0"], inputs["INPUT1"]
            return {"OUTPUT0": a + b, "OUTPUT1": a - b}
        return apply


class StringAddSubBackend(ModelBackend):
    """BYTES decimal-string add/sub — exercises the BYTES codec end to end.

    Host-side compute (object arrays can't enter XLA), like the reference's
    simple_string model served by a CPU backend.
    """

    jittable = False

    def __init__(self, name: str = "simple_string", n: int = 16):
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=8,
            input=[
                TensorConfig("INPUT0", "BYTES", [n]),
                TensorConfig("INPUT1", "BYTES", [n]),
            ],
            output=[
                TensorConfig("OUTPUT0", "BYTES", [n]),
                TensorConfig("OUTPUT1", "BYTES", [n]),
            ],
        )

    def make_apply(self):
        def apply(inputs):
            a = np.vectorize(int)(inputs["INPUT0"]).astype(np.int64)
            b = np.vectorize(int)(inputs["INPUT1"]).astype(np.int64)
            enc = np.vectorize(lambda v: str(v).encode())
            return {
                "OUTPUT0": enc(a + b).astype(np.object_),
                "OUTPUT1": enc(a - b).astype(np.object_),
            }
        return apply


class IdentityBackend(ModelBackend):
    """BYTES passthrough (`simple_identity`) — string round-trip oracle."""

    jittable = False

    def __init__(self, name: str = "simple_identity"):
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=8,
            input=[TensorConfig("INPUT0", "BYTES", [-1])],
            output=[TensorConfig("OUTPUT0", "BYTES", [-1])],
        )

    def make_apply(self):
        def apply(inputs):
            return {"OUTPUT0": inputs["INPUT0"]}
        return apply


class SequenceAccumulateBackend(ModelBackend):
    """Stateful accumulator (`simple_sequence` semantics): OUTPUT = running
    sum of INPUT across the sequence. State = INT32[1] pytree in HBM.

    ``strategy="oldest"`` serves the same model through the arena-batched
    oldest-sequence scheduler (steps of distinct sequences share one XLA
    execution; see engine/sequence.py OldestSequenceScheduler)."""

    def __init__(self, name: str = "simple_sequence",
                 strategy: str = "direct",
                 max_candidate_sequences: int = 64):
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=0,  # sequence requests are shape [1]
            input=[TensorConfig("INPUT", "INT32", [1])],
            output=[TensorConfig("OUTPUT", "INT32", [1])],
            sequence_batching=SequenceBatchingConfig(
                strategy=strategy,
                max_candidate_sequences=max_candidate_sequences),
        )

    def initial_state(self):
        return np.zeros((1,), dtype=np.int32)

    def make_apply(self):
        def apply(state, inputs):
            acc = state + inputs["INPUT"]
            return acc, {"OUTPUT": acc}
        return apply


class RepeatBackend(ModelBackend):
    """Decoupled model (`repeat_int32` semantics): emits IN's elements one
    response at a time, with DELAY microseconds between responses."""

    jittable = False

    def __init__(self, name: str = "simple_repeat"):
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=0,
            input=[
                TensorConfig("IN", "INT32", [-1]),
                TensorConfig("DELAY", "UINT32", [-1], optional=True),
            ],
            output=[
                TensorConfig("OUT", "INT32", [1]),
                TensorConfig("IDX", "UINT32", [1]),
            ],
            decoupled=True,
        )

    def make_apply(self):
        def apply(inputs):  # non-streaming fallback: first element only
            return {
                "OUT": inputs["IN"][:1],
                "IDX": np.zeros((1,), dtype=np.uint32),
            }
        return apply

    def generate(self, inputs: dict[str, np.ndarray],
                 parameters: dict[str, Any]) -> Iterator[dict[str, np.ndarray]]:
        import time

        data = np.ravel(inputs["IN"]).astype(np.int32)
        delays = np.ravel(inputs.get("DELAY", np.zeros(0, np.uint32)))
        for i, v in enumerate(data):
            if i < len(delays) and delays[i]:
                time.sleep(int(delays[i]) / 1e6)
            yield {
                "OUT": np.array([v], dtype=np.int32),
                "IDX": np.array([i], dtype=np.uint32),
            }


register_model("simple")(AddSubBackend)
register_model("simple_string")(StringAddSubBackend)
register_model("simple_identity")(IdentityBackend)
register_model("simple_sequence")(SequenceAccumulateBackend)
register_model("simple_sequence_oldest")(
    lambda: SequenceAccumulateBackend(name="simple_sequence_oldest",
                                      strategy="oldest"))
# INT8 add/sub variant (reference simple_int8 model, exercised by the
# explicit-content raw-stub clients).
register_model("simple_int8")(
    lambda: AddSubBackend(name="simple_int8", max_batch_size=8,
                          datatype="INT8"))
register_model("simple_repeat")(RepeatBackend)
