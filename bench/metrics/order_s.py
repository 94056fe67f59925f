"""Seconds of order preconditioning per query: host clock around
`compute_order`, ending in `block_until_ready` (traced runs only)."""


def read(ctx):
    spans = ctx.spans.get("order")
    if not spans:
        return None
    return sum(spans) / len(spans)
