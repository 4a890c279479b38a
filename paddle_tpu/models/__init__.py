"""Model zoo (capability parity with the ecosystem models the baseline
configs exercise — SURVEY §2.4: BERT, Llama, ERNIE-style, MoE decoders,
PP-OCR CNNs). Models are written against paddle_tpu.nn and are trace-ready."""

from . import bert  # noqa: F401
from . import deepseek  # noqa: F401
from . import gpt  # noqa: F401
from . import laguna  # noqa: F401
from . import llama  # noqa: F401
from . import moe_llm  # noqa: F401
from . import qwen2  # noqa: F401
