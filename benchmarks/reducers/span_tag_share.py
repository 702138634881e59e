"""Of the program's spans of one name that carry certain tag values,
the share, times ``scale``, whose tag ``tag`` equals ``equals``.
Parameters: ``span``, ``where`` ({tag: value}, all must hold; may be
empty), ``tag``, ``equals``, ``scale`` (100 for a share in percent).
The counts ride on spans because the harness's counter list is closed;
the reader notes both counts, and how many spans of the name failed
``where``, beside the share."""


def reduce(params: dict, ctx: dict):
    where = params.get("where", {})
    total = kept = hit = 0
    for s in ctx["spans"]:
        if s["name"] != params["span"]:
            continue
        total += 1
        tags = s.get("tags") or {}
        if any(tags.get(k) != v for k, v in where.items()):
            continue
        kept += 1
        hit += tags.get(params["tag"]) == params["equals"]
    if not kept:
        return None
    ctx["notes"].append(
        f"{params['span']}: {total} spans, {kept} with {where}, {hit} of "
        f"those with {params['tag']}={params['equals']}")
    return float(params.get("scale", 1)) * hit / kept
