"""Mean duration, in ms, of the program's spans of one name that began
inside the window.  Parameters: ``span`` (the name in ``obs/trace.py``'s
taxonomy), optionally ``tag`` (count only spans that carry this tag)."""


def reduce(params: dict, ctx: dict):
    tag = params.get("tag")
    durs = [s["dur"] for s in ctx["spans"] if s["name"] == params["span"]
            and (tag is None or tag in (s.get("tags") or {}))]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
