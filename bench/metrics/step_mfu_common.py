"""Model FLOPs of the real tokens the traced window processed, over traced
time x chips x the chip's bf16 peak (percent).

The FLOPs a token costs come from the configuration's reference module
(its ``model_flops``), which ``bench/run.py`` puts in ``run["model_flops"]``:
per real token the step processes, and per token it emits."""


def step_mfu(run):
    tr, tok = run.get("trace"), run.get("traced")
    if not tr or not tok or not tr.get("window_s") or not tok["real_tokens"]:
        return None
    per_real, per_emitted = run["model_flops"]
    flops = per_real * tok["real_tokens"] + per_emitted * tok["emitted"]
    peak = run["peak"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / (tr["window_s"] * peak)
