"""The model zoo.

Importing this package registers every model builder with the registry
(``utils.registry``); the engine resolves builders by ``ModelConfig.name``.
Zoo contents mirror the five BASELINE configs (SURVEY §0): ResNet-18,
ResNet-50, EfficientNet-B0, BERT-base, Whisper-tiny, SD-1.5 — plus
ViT-B/16, GPT-2, EvaByte, Nemotron-H, LFM2, Mellum 2 and JoyAI-LLM-Flash text
generation (beyond the reference).
"""

from . import resnet  # noqa: F401

# Models added as the zoo grows; each import is guarded so a broken optional
# model cannot take down serving of the others.
for _mod in ("efficientnet", "bert", "whisper", "sd15", "vit", "gpt2",
             "evabyte", "nemotron_h", "lfm2", "mellum", "joyai"):
    try:
        __import__(f"{__name__}.{_mod}")
    except ImportError:
        pass
