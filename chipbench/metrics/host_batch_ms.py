"""Data pipeline: mean host time of ``next_batch`` per window step, from the
harness's own span around the supervisor's call into the pipeline."""


def read(run):
    spans = run.spans.get("next_batch", [])
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
