"""Window pages a sequence holds in each window layer, from the attributes
the program's ``ds/serving/build`` spans carry for a model with a window
group (``window_pages``, ``state_slots``): the median over the window's
dispatches of pages held over sequences holding state. A ring that frees
reads ``window / block + 1`` to ``+ chunk / block``; a number that grows with
the context says pages are kept. None where the spans lack the attributes."""

import statistics

from benchmark import program_spans as ps


def read(ctx, params):
    loaded = ps.for_run(ctx)
    if loaded is None:
        return None
    per_seq = [s[3]["window_pages"] / s[3]["state_slots"] for s in ps.named(loaded, ps.BUILD)
               if s[3].get("state_slots") and "window_pages" in s[3]]
    if not per_seq:
        return None
    freed = sum(s[3].get("window_pages_freed", 0) for s in ps.named(loaded, ps.BUILD))
    ctx["notes"].append(f"window_pages: {len(per_seq)} dispatches, {min(per_seq):.2f} to "
                        f"{max(per_seq):.2f} pages a sequence a layer, {freed} pages freed "
                        f"behind the window")
    return statistics.median(per_seq)
