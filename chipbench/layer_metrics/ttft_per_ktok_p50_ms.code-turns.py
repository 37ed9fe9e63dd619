"""Due time -> first token per 1,000 prompt tokens, the median over the
window's requests (20 at 51 s). A prompt is prefilled 128 tokens a step, so
the quotient is the price of a chunk step on that request's path, whatever
``prefill_chunk`` is, and the median does not follow the few requests that
met a pool-wide program or waited for a slot. ISSUE 30 proposed it as this
cell's end-to-end first-token metric if it held 5 % in each of three sets
of seeded arrangements; it read 3.1 / 7.2 / 4.6 %, so it stands here, beside
the end-to-end ``ttft_mean_ms`` (PERF.md section 6, PR 30)."""


def read(view):
    return view.record["e2e"].get("ttft_per_ktok_p50_ms")
