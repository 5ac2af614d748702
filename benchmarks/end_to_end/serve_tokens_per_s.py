"""Tokens served in the window over its wall time, from the client's side:
the tokens of a request's new turn count when its first token lands, each
generated token when it lands. The context of the session a turn belongs to
was served in set-up and is not the window's work: it counts nowhere."""


def read(run):
    rec = run.record
    if rec["kind"] != "serve":
        return None
    return rec["tokens"] / rec["wall_s"]
