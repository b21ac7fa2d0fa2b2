"""KV page bytes the decode read per output token: pages gathered
(active slots x bucket, the engine's counter) times the page size from
the configuration's shapes, over the tokens of the window."""

from chipbench import work


def read(run):
    pages = run.counters.get("decode_page_reads", 0)
    n = sum(len(r.times) for r in run.requests)
    if not pages or not n:
        return None
    return pages * work.page_bytes(run.config, run.cell["page_tokens"]) / n
