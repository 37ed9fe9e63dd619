"""Due time -> first token per 1,000 prompt tokens, the median over the
window's requests. A prompt is prefilled a chunk a step, so the quotient is
the price of a chunk step on that request's path, whatever
``prefill_chunk`` is, and the median does not follow the few requests that
met a pool-wide program or waited for a slot (PERF.md section 6, PR 30)."""


def read(view):
    return view.record["e2e"].get("ttft_per_ktok_p50_ms")
