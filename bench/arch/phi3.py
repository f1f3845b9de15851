"""The decoder the program runs for a ``model_type`` ``phi3``
configuration: the dense grouped-query decoder of ``internlm2`` with tied
embeddings. Its positional scheme is not Phi-3's: it rotates whole heads
and has no LongRoPE, so a Phi-4-mini configuration runs as a decoder of
Phi-4-mini's widths with whole-head RoPE. ``program_config`` refuses a
partial rotary factor; the configuration lists the published one in its
``reduced``."""
from bench.arch.internlm2 import (  # noqa: F401
    loss, matmul_params, param_shapes, program_config, train_flops_per_token)
