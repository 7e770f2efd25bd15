"""Planted waiver twin: the same module-level import, waived with a reason;
and the function-local form, which the rule leaves alone."""
# timm-tpu-lint: disable=layering planted fixture proving the line-scope waiver
import timm_tpu.autotune


def plan(args):
    from timm_tpu.autotune import autotune
    return autotune(args.model, {}, global_batch=args.batch_size), timm_tpu.autotune
