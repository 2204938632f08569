"""Host time of one decode step: between two successive tokens on the host."""


def read(run):
    spans = run.spans.get("decode_step")
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
