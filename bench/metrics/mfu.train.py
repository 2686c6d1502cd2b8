"""Whole train step (``launch/train.train_loop`` -> ``train/train_step`` ->
``models/*``): model FLOPs per token (forward and backward from the
configuration's shapes, attention included, recompute not counted; see
``bench/flops.py``) times the window's tokens per second, over the chips'
bf16 peak, in %."""


def read(ctx):
    obs = ctx["obs"]
    if not obs.get("flops_per_token"):
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["cell"].chips
    return obs["flops_per_token"] * ctx["metrics"]["train_tokens_per_s"] / peak * 100.0
