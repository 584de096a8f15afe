"""KV: peak share of the pool's usable pages that LIVE requests hold
(private pages and shared prefix pages some request maps), sampled
after every step's page work — `kv_pool_peak` less the prefix pages
the radix cache merely retains: memory in use against memory
reserved."""

from cellbench import span_reader


def read(run):
    steps = span_reader.steps_of(run, "kv_live_peak")
    if steps is None:
        return None
    live = [st.phase_attrs[span_reader.PAGES]["live_pages"]
            for st in steps
            if "live_pages" in st.phase_attrs.get(span_reader.PAGES, {})]
    if not live:
        return None
    return 100.0 * max(live) / run.system.usable_pages
