"""Model FLOP/s utilisation: the FLOPs the forward and backward passes require
per token (``peaks.<flops_fn>``) x tokens/s, over chips x the table's peak.
Recomputed operations are not counted."""

from benchmark import peaks


def read(ctx, params):
    facts = ctx["facts"]
    if not facts.get("tokens_per_s"):
        return None
    per_token = getattr(peaks, params["flops_fn"])(ctx["cell"].config, facts["seq"])
    return 100.0 * per_token * facts["tokens_per_s"] / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
