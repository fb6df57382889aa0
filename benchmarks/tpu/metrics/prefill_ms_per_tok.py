"""prefill_ms_per_tok (ms, entry layer: the prompt phase): wall time of
the ``bench.prompt`` host spans (cache reset, prompt replay through the
decode step, sync on the first generated token) per prompt position."""


def read(ctx):
    spans = ctx.trace.spans.get("bench.prompt", [])
    if not spans:
        return None
    total = sum(s.dur for s in spans)
    return 1e3 * total / (len(spans) * ctx.traffic["prompt_len"])
