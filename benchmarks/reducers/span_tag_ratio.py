"""A ratio of sums over the program's spans, times ``scale``:

    scale x  sum over terms, over a term's spans, of prod(numerator)
          /  sum over terms, over a term's spans, of prod(denominator)

A term is ``{"span", "where", "numerator", "denominator"}``: the spans
of that name whose tags hold every ``where`` value and carry every tag
the two lists name.  A list holds tag names, multiplied together;
``"dur"`` stands for the span's duration in seconds; an empty list is 1,
so an empty denominator counts the spans and the ratio is a mean.  A
span that lacks a named tag is left out, never counted as nought: a
program that does not write the tag reads nothing.  Nothing to divide
by: nothing.  The note gives each term's spans and sums.  Parameters:
``terms``, ``scale`` (1e3 for seconds to ms, 100 for a share in
percent)."""


def _product(span: dict, tags: dict, names: list) -> float:
    out = 1.0
    for name in names:
        out *= span["dur"] if name == "dur" else tags[name]
    return out


def reduce(params: dict, ctx: dict):
    top = bottom = 0.0
    for term in params["terms"]:
        where = term.get("where", {})
        needs = [n for n in term["numerator"] + term["denominator"]
                 if n != "dur"]
        kept = 0
        num = den = 0.0
        for s in ctx["spans"]:
            if s["name"] != term["span"]:
                continue
            tags = s.get("tags") or {}
            if any(tags.get(k) != v for k, v in where.items()) \
                    or any(n not in tags for n in needs):
                continue
            kept += 1
            num += _product(s, tags, term["numerator"])
            den += _product(s, tags, term["denominator"])
        ctx["notes"].append(
            f"{term['span']}: {kept} spans with {where} and {needs}; "
            f"sum of {'*'.join(term['numerator']) or '1'} {num:.6g} over "
            f"sum of {'*'.join(term['denominator']) or '1'} {den:.6g}")
        top += num
        bottom += den
    if bottom <= 0:
        return None
    return float(params.get("scale", 1)) * top / bottom
