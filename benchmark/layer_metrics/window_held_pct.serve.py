"""Pages the windowed page groups held at their fullest, over the pages
the same requests' block tables spanned at that moment (what they would
have held with nothing released): from ``ServingEngine.stats()``'s
``page_groups`` (warm traffic included). 100 means the window gave nothing
back; the lower, the more of a long context's pages a window layer does
not keep. An engine without a windowed group gives nothing to read."""
LAYER = "scheduler and cache"
MOVES = "itl_p99_ms"


def read(run):
    groups = [g for g in (run.counters.get("page_groups") or {}).values()
              if g.get("window") is not None]
    spanned = sum(g["peak_unreleased"] for g in groups)
    if not spanned:
        return None
    return 100.0 * sum(g["peak_held"] for g in groups) / spanned
