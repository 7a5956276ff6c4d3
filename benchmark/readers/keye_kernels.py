"""Per-layer metrics of Keye-VL-2.0's learned sparse attention, from the
device trace, with the work from ``benchmark/peaks_keye.py``.

params: ``work`` one of

- ``dsa_index`` and ``dsa_read`` (roofline shares): the device time of the
  events that ran under the ``jax.named_scope``s ``scopes`` (found through the
  operations' ``op_name`` in the trace file, ``benchmark/xplane_scopes.py``:
  the kernels and every layout change beside them), against the least time
  of the rows the window's rounds ran past ``topk`` tokens, each row
  ``max(FLOPs / peak, bytes / peak)`` of its new tokens and the position it
  ended at (``attn_rows`` of the harness's ``round`` spans). For ``dsa_index``
  the scopes are ``dsa_index`` and ``dsa_select`` (the selection has no work
  of its own that a peak bounds, so its time counts against the scores'); for
  ``dsa_read`` the work is that of the tokens SELECTED, so a walk that reads
  every page reads a low share, as it should;
- ``dsa_share`` (a share of the device's busy time): the device time under
  the scope ``dsa_attn``, by the scope below it, over the busy time of the
  traced window. Its note gives ``moe_ffn``'s share beside it, and what the
  ``serving/build`` spans say the selection had to read.

Each says in a note what the number rests on. A trace without the events, the
scopes or the attributes (a program from before this model) gives None.
"""

import collections
import re

from benchmark import peaks_keye as work, program_spans as ps
from benchmark.readers.kanana2_kernels import _scoped
from benchmark.readers.mellum2_kernels import _least

PARTS = re.compile(r"/(dsa_qkv|dsa_write|dsa_index|dsa_select|dsa_read|dsa_out)/")
WORK_OF = {"dsa_index": (work.dsa_index_flops, work.dsa_index_bytes),
           "dsa_read": (work.dsa_read_flops, work.dsa_read_bytes)}


def _rows(ctx):
    rounds = [attrs["attn_rows"] for name, _, _, attrs in ctx["spans"]
              if name == "round" and "attn_rows" in attrs]
    return rounds, [row for r in rounds for row in r]


def roofline(ctx, params):
    cfg = ctx["cell"].config
    scoped = _scoped(ctx)
    rounds, rows = _rows(ctx)
    topk = cfg["sa_config"]["topk"]
    rows = [(new, end) for new, end in rows if end > topk]
    if not scoped or not rows:
        return None
    took = {s: sum(t for _, op, t in scoped if f"/{s}/" in op) for s in params["scopes"]}
    if not sum(took.values()):
        return None
    flops, nbytes = WORK_OF[params["work"]]
    least, compute = _least([(flops(cfg, new, end), nbytes(cfg, new, end))
                             for new, end in rows], ctx["peaks"])
    decode = [end for new, end in rows if new == 1]
    chunks = [end for new, end in rows if new > 1]
    ctx["notes"].append(
        f"keye_kernels {params['work']}: "
        + ", ".join(f"{t:.4f} s under {s!r}" for s, t in took.items())
        + f", least {least:.4f} s; {len(rows)} rows past {topk} tokens in {len(rounds)} "
        f"rounds, {compute} compute-bound, {len(decode)} decode rows at contexts of "
        f"{min(decode, default=0)}-{max(decode, default=0)}, {len(chunks)} chunks ending at "
        f"{min(chunks, default=0)}-{max(chunks, default=0)}")
    return 100.0 * least / sum(took.values())


def dsa_share(ctx, params):
    busy = ctx["summary"]["busy_s"]
    scoped = _scoped(ctx)
    if not scoped or not busy:
        return None
    parts, total = {}, 0.0
    for _, op, took in scoped:
        if "/dsa_attn/" not in op:
            continue
        m = PARTS.search(op)
        part = m.group(1) if m else "rest"
        parts[part] = parts.get(part, 0.0) + took
        total += took
    if not total:
        return None
    experts = sum(t for _, op, t in scoped if "/moe_ffn/" in op)
    loaded = ps.for_run(ctx)
    builds = [s[3] for s in ps.named(loaded, ps.BUILD)] if loaded else []
    builds = [a for a in builds if "selected_tokens" in a]
    note = ""
    if builds:
        pages = [int(a["index_pages"]) for a in builds]
        note = (f"; {len(builds)} dispatches, {sum(int(a['sparse_rows']) for a in builds)} "
                f"sparse rows, {sum(int(a['selected_tokens']) for a in builds)} (token, layer) "
                f"reads selected, index pages held {min(pages)}-{max(pages)}"
                + _counters_against_rows(ctx, builds))
    ctx["notes"].append(
        f"keye_kernels dsa_share: {total:.4f} s of {busy:.4f} s busy under 'dsa_attn': "
        + ", ".join(f"{k} {v:.4f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1]))
        + f"; beside it moe_ffn {experts:.4f} s = {100 * experts / busy:.2f} %" + note)
    return 100.0 * total / busy


def _counters_against_rows(ctx, builds):
    """Whether the spans' ``sparse_rows`` and ``selected_tokens``, summed a
    round, are what the harness's rows of that round give from their lengths
    alone: the rounds of the window whose pair of sums is among the spans'
    (the trace may hold a round or two the window does not)."""
    cfg = ctx["cell"].config
    topk, layers = cfg["sa_config"]["topk"], cfg["num_hidden_layers"]
    spans = {}
    for a in builds:
        got = spans.setdefault(int(a["round"]), [0, 0])
        got[0] += int(a["sparse_rows"])
        got[1] += int(a["selected_tokens"])
    left = collections.Counter(tuple(v) for v in spans.values())
    rounds, found = _rows(ctx)[0], 0
    for rows in rounds:
        want = (sum(end > topk for _, end in rows),
                layers * sum(work.selected(cfg, new, end) for new, end in rows))
        if left[want] > 0:
            left[want] -= 1
            found += 1
    return (f"; the spans' sparse_rows and selected_tokens a round are the rows' own count "
            f"from their lengths in {found} of the window's {len(rounds)} rounds")


WORK = {"dsa_index": roofline, "dsa_read": roofline, "dsa_share": dsa_share}


def read(ctx, params):
    if ctx["trace"] is None:
        return None
    return WORK[params["work"]](ctx, params)
