from deepspeed_tpu.models.llama import (LLAMA_CONFIGS, LlamaConfig, LlamaForCausalLM, build_llama,
                                        causal_lm_loss, llama_tp_rule)  # noqa: F401
from deepspeed_tpu.models.gpt import (GPT_CONFIGS, GPTConfig, GPTForCausalLM, build_gpt,
                                      gpt_tp_rule, init_gpt_cache)  # noqa: F401
from deepspeed_tpu.models.bert import (BERT_CONFIGS, BertConfig, BertForMaskedLM,
                                       BertForSequenceClassification, bert_tp_rule,
                                       build_bert)  # noqa: F401
from deepspeed_tpu.models.moonlight import (MOONLIGHT_CONFIGS, MoonlightConfig,
                                            MoonlightForCausalLM, build_moonlight)  # noqa: F401
from deepspeed_tpu.models.longcat import (LONGCAT_CONFIGS, LongcatFlashConfig,
                                          LongcatFlashForCausalLM, build_longcat)  # noqa: F401
from deepspeed_tpu.models.minicpm_sala import (MINICPM_SALA_CONFIGS, MiniCPMSalaConfig,
                                               MiniCPMSalaForCausalLM,
                                               build_minicpm_sala)  # noqa: F401
from deepspeed_tpu.models.lfm2 import (LFM2_CONFIGS, Lfm2MoeConfig, Lfm2MoeForCausalLM,
                                       build_lfm2)  # noqa: F401
from deepspeed_tpu.models.jamba import (JAMBA_CONFIGS, JambaConfig, JambaForCausalLM,
                                        build_jamba)  # noqa: F401
from deepspeed_tpu.models.nemotron_h import (NEMOTRON_H_CONFIGS, NemotronHConfig,
                                             NemotronHForCausalLM,
                                             build_nemotron_h)  # noqa: F401
from deepspeed_tpu.models.solar_open2 import (SOLAR_OPEN2_CONFIGS, SolarOpen2Config,
                                              SolarOpen2ForCausalLM,
                                              build_solar_open2)  # noqa: F401
from deepspeed_tpu.models.laguna import (LAGUNA_CONFIGS, LagunaConfig, LagunaForCausalLM,
                                         build_laguna)  # noqa: F401
from deepspeed_tpu.models.ouro import (OURO_CONFIGS, OuroConfig, OuroForCausalLM,
                                       build_ouro)  # noqa: F401
from deepspeed_tpu.models.granite_hybrid import (GRANITE_HYBRID_CONFIGS, GraniteHybridConfig,
                                                 GraniteHybridForCausalLM,
                                                 build_granite_hybrid)  # noqa: F401
from deepspeed_tpu.models.mellum import (MELLUM_CONFIGS, MellumConfig,
                                         build_mellum)  # noqa: F401  (trained, not served)

# The causal-LM families a preset name can build, in the order names are looked up
# (the v2 serving engine takes any of them: inference/v2/model_runner.kind_of).
MODEL_REGISTRY = ((LLAMA_CONFIGS, build_llama), (GPT_CONFIGS, build_gpt),
                  (MOONLIGHT_CONFIGS, build_moonlight), (LONGCAT_CONFIGS, build_longcat),
                  (MINICPM_SALA_CONFIGS, build_minicpm_sala),
                  (NEMOTRON_H_CONFIGS, build_nemotron_h), (LFM2_CONFIGS, build_lfm2),
                  (JAMBA_CONFIGS, build_jamba), (SOLAR_OPEN2_CONFIGS, build_solar_open2),
                  (LAGUNA_CONFIGS, build_laguna), (OURO_CONFIGS, build_ouro),
                  (GRANITE_HYBRID_CONFIGS, build_granite_hybrid))


def build_model(preset, **overrides):
    """A causal LM by its preset's name, whichever family holds it."""
    for presets, build in MODEL_REGISTRY:
        if preset in presets:
            return build(preset, **overrides)
    known = sorted(name for presets, _ in MODEL_REGISTRY for name in presets)
    raise KeyError(f"no model preset {preset!r}; known: {known}")
