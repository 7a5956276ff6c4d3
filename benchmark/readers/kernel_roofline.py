"""A kernel's share of its roofline from the device trace: the least time the
chip could take for the work (``max(FLOPs/peak, bytes/peak)``, both from
shapes by a function in ``benchmark.peaks``) over the time its events took.

params: ``match`` a regular expression on the event's HLO text; ``work`` how
the work is counted: ``flash_train`` (per counted training step, from the
cell's batch and the configuration's heads) or ``paged_decode`` (the KV bytes
of the decode-only rounds inside the traced window; only kernel events inside
those rounds' spans are timed). Says which bound it is in a note."""

from benchmark import peaks, trace


def read(ctx, params):
    tr, facts, cfg = ctx["trace"], ctx["facts"], ctx["cell"].config
    if tr is None:
        return None
    if params["work"] == "flash_train":
        events = trace.kernel_events(tr, params["match"])
        steps = facts.get("steps")
        if not events or not steps:
            return None
        shape = (facts["batch"], cfg["n_head"], facts["seq"], cfg["n_embd"] // cfg["n_head"])
        flops = steps * cfg["n_layer"] * peaks.flash_train_flops(*shape)
        nbytes = steps * cfg["n_layer"] * peaks.flash_train_bytes(*shape)
    elif params["work"] == "paged_decode":
        # the trace's bench/round annotations and the recorder's round spans
        # are the same spans in the same order
        traced = trace.spans_named(tr, "bench/round")
        rounds = [attrs for name, _, _, attrs in ctx["spans"] if name == "round"]
        if not traced or len(traced) != len(rounds):
            ctx["notes"].append(f"kernel_roofline paged_decode: {len(traced)} traced rounds "
                                f"for {len(rounds)} recorded, nothing read")
            return None
        decode = [(iv, attrs) for iv, attrs in zip(traced, rounds)
                  if attrs.get("prefill_tokens") == 0]
        events = trace.kernel_events(tr, params["match"], within=[iv for iv, _ in decode])
        if not events:
            return None
        context = sum(attrs["context_tokens"] for _, attrs in decode)
        dims = (cfg["num_hidden_layers"], cfg["num_key_value_heads"],
                cfg["hidden_size"] // cfg["num_attention_heads"])
        nbytes = peaks.paged_decode_bytes(context, *dims)
        flops = peaks.paged_decode_flops(context, cfg["num_hidden_layers"],
                                         cfg["num_attention_heads"], dims[2])
    else:
        raise ValueError(f"unknown work {params['work']!r}")
    least, bound = peaks.roofline_seconds(flops, nbytes, ctx["peaks"])
    took = sum(b - a for a, b in events) / 1e9
    ctx["notes"].append(f"kernel_roofline {params['work']}: {len(events)} events, "
                        f"{took:.4f} s on the device, least {least:.4f} s ({bound}-bound)")
    return 100.0 * least / took
