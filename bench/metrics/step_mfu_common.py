"""Model FLOPs of the real tokens the traced window processed, over traced
time x chips x the chip's bf16 peak (percent).

Counted per real token: two FLOPs per weight of every layer's matrices;
per emitted token: two per weight of the output head (the step computes
logits only for sampled rows). Attention's score and value products are
left out (a few percent at these lengths), so this is a lower bound."""


def step_mfu(run):
    tr, tok = run.get("trace"), run.get("traced")
    if not tr or not tok or not tr.get("window_s") or not tok["real_tokens"]:
        return None
    s = run["sizes"]
    d, ff = s["hidden_size"], s["intermediate_size"]
    hd = s.get("head_dim") or d // s["num_attention_heads"]
    q, kv = s["num_attention_heads"] * hd, s["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    flops = (2 * per_layer * s["num_hidden_layers"] * tok["real_tokens"]
             + 2 * s["vocab_size"] * d * tok["emitted"])
    peak = run["peak"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / (tr["window_s"] * peak)
