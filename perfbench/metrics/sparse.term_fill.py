"""Share of the sparse query term slots the fused kernel matched that
held a live term: ``query_terms_total`` (live ids served) over
``query_term_slots_total`` (batch size times slots, per executed batch,
pad rows included), ``ServingStats``' exact counters over the traced
window.  The rest is term-match work spent on padding."""


def read(layers):
    slots = getattr(layers.stats, "query_term_slots_total", 0)
    if not slots:
        return None
    return 100.0 * layers.stats.query_terms_total / slots
