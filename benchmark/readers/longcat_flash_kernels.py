"""Per-layer metrics of LongCat-Flash-Chat's double layer, from the device
trace and the program's device counters, with the work from
``benchmark/peaks_longcat_flash.py``.

params: ``work`` one of

- ``scmoe_mla`` (a roofline share): ``kanana2_kernels.mla_attn`` as it is (the
  events matching ``match``, the latent walk ``paged_mla``, and the further
  events under ``scope``, here both attentions' ``mla_read``) against the
  lesser-form count of ``peaks_kanana2`` at this configuration's 64 heads and
  its ``2 x num_layers`` planes;
- ``scmoe_gmm`` (a roofline share): the events matching ``match`` (megablox's
  ``gmm``) against ``max(FLOPs / peak, bytes / peak)`` of what the window's
  dispatches really routed: the driver's ``facts["device_counters"]``, the
  program's own count on the device of the rows that landed on a held expert
  (``held_rows``) and of the held experts with at least one row
  (``experts_hit``), NOT a guess from uniform routing;
- ``scmoe_dense_ffn`` (a roofline share): the device time of the events under
  ``scope`` (the two dense FFNs' products and activations) against the least
  time of the window's dispatches, each ``max(FLOPs / peak, bytes / peak)`` of
  its real tokens (``ds/serving/build``): a chunk is compute-bound, a decode
  dispatch reads the FFNs' weights;
- ``zero_share``: the counters' ``zero_rows / routed_rows``, in percent: a
  property of weights and traffic, printed so that a change in it is seen as
  a change of work, not of speed.

Each says in a note what the number rests on. A run without the counters, the
events or the scopes (a program from before this model) gives None.
"""

import re

from benchmark import peaks, peaks_longcat_flash as work, program_spans as ps, trace
from benchmark.readers import kanana2_kernels
from benchmark.readers.mellum2_kernels import _least, _took


class _CellAs:
    """The cell under another reading of its configuration's keys."""

    def __init__(self, cell, config):
        self.name, self.config = cell.name, config


def scmoe_mla(ctx, params):
    kanana2_kernels._scoped(ctx)          # read once, kept in ``ctx`` for every reader
    return kanana2_kernels.mla_attn(
        dict(ctx, cell=_CellAs(ctx["cell"], work.mla_config(ctx["cell"].config))), params)


def _counters(ctx):
    counts = (ctx.get("facts") or {}).get("device_counters") or {}
    return counts if counts.get("routed_rows") else None


def scmoe_gmm(ctx, params):
    counts = _counters(ctx)
    events = trace.kernel_events(ctx["trace"], params["match"])
    if not events or counts is None:
        return None
    cfg = ctx["cell"].config
    flops = work.moe_gmm_flops(cfg, counts["held_rows"])
    nbytes = work.moe_gmm_bytes(cfg, counts["experts_hit"], counts["held_rows"])
    least, bound = peaks.roofline_seconds(flops, nbytes, ctx["peaks"])
    took = _took(events)
    layers = max(counts["dispatches"] * cfg["num_layers"], 1)
    ctx["notes"].append(
        f"longcat_flash_kernels scmoe_gmm: {len(events)} events, {took:.4f} s on the device, "
        f"least {least:.4f} s ({bound}-bound) from the device counters: {counts['held_rows']} "
        f"rows landed on held experts of {counts['routed_rows']} routed in "
        f"{counts['dispatches']} dispatches, {counts['experts_hit'] / layers:.2f} of "
        f"{cfg['n_routed_experts']} held experts hit a layer and dispatch")
    return 100.0 * least / took


def scmoe_dense_ffn(ctx, params):
    cfg = ctx["cell"].config
    loaded = ps.for_run(ctx)
    tokens = [int(s[3]["real_tokens"]) for s in ps.named(loaded, ps.BUILD)] if loaded else []
    scoped = kanana2_kernels._scoped(ctx)
    if not scoped or not tokens:
        return None
    under = re.compile(params["scope"])
    took = sum(t for _, op, t in scoped if under.search(op))
    if not took:
        return None
    least, compute = _least([(work.dense_ffn_flops(cfg, t), work.dense_ffn_bytes(cfg, t))
                             for t in tokens], ctx["peaks"])
    ctx["notes"].append(
        f"longcat_flash_kernels scmoe_dense_ffn: {took:.4f} s on the device under "
        f"{params['scope']!r}, least {least:.4f} s ({compute} of {len(tokens)} dispatches "
        f"compute-bound, the rest read the weights); {sum(tokens)} real tokens")
    return 100.0 * least / took


def zero_share(ctx, params):
    counts = _counters(ctx)
    if counts is None:
        return None
    ctx["notes"].append(
        f"longcat_flash_kernels zero_share: {counts['zero_rows']} of {counts['routed_rows']} "
        f"routed rows took a zero expert, {counts['held_rows']} landed on a held one")
    return 100.0 * counts["zero_rows"] / counts["routed_rows"]


WORK = {"scmoe_mla": scmoe_mla, "scmoe_gmm": scmoe_gmm, "scmoe_dense_ffn": scmoe_dense_ffn,
        "zero_share": zero_share}


def read(ctx, params):
    if params["work"] != "zero_share" and ctx["trace"] is None:
        return None
    return WORK[params["work"]](ctx, params)
