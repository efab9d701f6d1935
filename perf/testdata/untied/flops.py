"""Operations and bytes of the harness tests' second architecture. Its
block multiplies through the same matrices as Qwen2's (biases are not
counted there) and its untied head has the tied one's size, so Qwen2's
counts hold as they are."""
from perf.flops.qwen2 import prefill_lane_bytes, serving_flops  # noqa: F401
