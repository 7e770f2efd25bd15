"""Planted violation: program code that imports a CPU tool when it is imported."""
from timm_tpu.perfbudget import probe_config


def step_cost(cfg):
    return probe_config(cfg)
