# LM-family model zoo: a single functional Model (models/model.py) driven by
# ArchConfig (models/config.py) covering dense GQA transformers, MoE
# (GShard-dispatch), Mamba/xLSTM recurrent mixers, the Jamba hybrid layout,
# encoder-only audio backbones, the Qwen2-VL M-RoPE VLM backbone, and
# DeepSeek-V3 stacks (MLA + dropless held-expert MoE; deepseek_ref.py is
# their float32 reference).

from repro.models.config import ArchConfig  # noqa: F401
from repro.models.model import Model  # noqa: F401
