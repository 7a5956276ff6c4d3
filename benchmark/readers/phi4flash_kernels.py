"""Roofline shares of Phi-4-mini-flash-reasoning's kernels from the device
trace, matched by the kernel's own name (the ``name=`` of its
``pallas_call``), with the work from ``benchmark/peaks_phi4flash.py``.

params: ``match`` a regular expression on the event's name; ``work``:

- ``selective_scan``: every matching event in the window against the state
  and tokens of the window's dispatches, read from the program's
  ``ds/serving/build`` spans (``seqs``, ``real_tokens``);
- ``hybrid_decode``: matching events inside the decode-only rounds (the
  harness's ``round`` spans with no prompt tokens) against the K and V bytes
  those rounds must read: ``context_tokens`` for the layers that read the full
  layer's pages, ``window_context_tokens`` (the new driver's attribute) for
  the window layers.

Says which bound it is in a note. A trace without the events or the
attributes gives None.
"""

from benchmark import peaks, peaks_phi4flash as work, program_spans as ps, trace


def read(ctx, params):
    tr, cfg = ctx["trace"], ctx["cell"].config
    if tr is None:
        return None
    if params["work"] == "selective_scan":
        loaded = ps.for_run(ctx)
        builds = ps.named(loaded, ps.BUILD) if loaded else []
        events = trace.kernel_events(tr, params["match"])
        if not events or not builds:
            return None
        rows = sum(s[3].get("seqs", 0) for s in builds)
        tokens = sum(s[3].get("real_tokens", 0) for s in builds)
        flops = work.selective_scan_flops(cfg, tokens)
        nbytes = work.selective_scan_bytes(cfg, rows, tokens)
        what = f"{len(builds)} dispatches of {rows} rows and {tokens} real tokens"
    elif params["work"] == "hybrid_decode":
        traced = trace.spans_named(tr, "bench/round")
        rounds = [attrs for name, _, _, attrs in ctx["spans"] if name == "round"]
        if not traced or len(traced) != len(rounds):
            return None
        decode = [(iv, a) for iv, a in zip(traced, rounds)
                  if a.get("prefill_tokens") == 0 and "window_context_tokens" in a]
        events = trace.kernel_events(tr, params["match"], within=[iv for iv, _ in decode])
        if not events:
            return None
        context = sum(a["context_tokens"] for _, a in decode)
        reach = sum(a["window_context_tokens"] for _, a in decode)
        flops = work.hybrid_decode_flops(cfg, context, reach)
        nbytes = work.hybrid_decode_bytes(cfg, context, reach)
        what = (f"{len(decode)} decode rounds, {context} context tokens, {reach} of them "
                f"in the windows' reach")
    else:
        raise ValueError(f"unknown work {params['work']!r}")
    least, bound = peaks.roofline_seconds(flops, nbytes, ctx["peaks"])
    took = sum(b - a for a, b in events) / 1e9
    ctx["notes"].append(f"phi4flash_kernels {params['work']}: {len(events)} events, {took:.4f} s "
                        f"on the device, least {least:.4f} s ({bound}-bound); {what}")
    return 100.0 * least / took
