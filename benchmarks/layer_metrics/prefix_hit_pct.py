"""Pool: of the session tokens that the prompts admitted in the window held,
the share that the prefix cache covered (``req.admit`` events of the
program's request-trace ring carry the matched tokens)."""


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or not rec.get("session_tokens_offered"):
        return None
    return 100.0 * rec["prefix_matched_tokens"] / rec["session_tokens_offered"]
