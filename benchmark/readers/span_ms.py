"""Median (or another percentile) of a harness span's duration, in ms.

params: ``span`` the span's name; ``percentile`` (default 50); ``where`` an
optional {attribute: [low, high]} filter on the span's attributes (either end
may be null)."""

from benchmark.harness import percentile


def read(ctx, params):
    def keep(attrs):
        for key, (lo, hi) in params.get("where", {}).items():
            v = attrs.get(key)
            if v is None or (lo is not None and v < lo) or (hi is not None and v > hi):
                return False
        return True

    ms = [(b - a) * 1e3 for name, a, b, attrs in ctx["spans"]
          if name == params["span"] and keep(attrs)]
    if not ms:
        return None
    ctx["notes"].append(f"span_ms {params['span']} {params.get('where', {})}: {len(ms)} spans")
    return percentile(ms, params.get("percentile", 50))
