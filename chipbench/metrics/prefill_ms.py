"""Host time of one prefill: from its dispatch to its first tokens on the host."""


def read(run):
    spans = run.spans.get("prefill")
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
