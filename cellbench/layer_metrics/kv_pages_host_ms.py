"""KV: host time of a step's page work — mapping the pages the
dispatch writes (evicting retained prefix pages, preempting) and
re-shipping the page table — the 95th percentile over the window's
steps of `serving.pages`: the steps that evict are the tail."""

from cellbench import span_reader, stats


def read(run):
    steps = span_reader.steps_of(run, "kv_pages_host_ms")
    if steps is None:
        return None
    with_pages = [st for st in steps if span_reader.PAGES in st.phases]
    if not with_pages:
        return None
    work = [st.phase_attrs[span_reader.PAGES] for st in with_pages]
    span_reader.say(
        event="kv_pages", steps=len(with_pages),
        **{k: sum(w.get(k, 0) for w in work)
           for k in ("mapped", "evicted", "preempted", "flushed_rows")})
    return stats.percentile(
        [st.phases[span_reader.PAGES] for st in with_pages], 95) * 1e3
