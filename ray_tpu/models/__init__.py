"""Model families.  The LM families (gpt, llama, axk1, evabyte, dots3,
falconh1, nemotronh, afmoe, lfm2, kimilinear, mellum) are specs of one decoder
(models/decoder.py); `family` is where a name becomes one."""

import importlib

LM_FAMILIES = ("gpt", "llama", "axk1", "evabyte", "dots3", "falconh1",
               "nemotronh", "afmoe", "lfm2", "kimilinear", "mellum")


def family(model):
    """The family module named `model`.  Anything else is taken as it is: a
    family module, or what `decoder.bind` makes of a spec."""
    if not isinstance(model, str):
        return model
    if model not in LM_FAMILIES:
        raise ValueError(f"unknown model family {model!r}")
    return importlib.import_module(f"ray_tpu.models.{model}")
