"""A ratio over the timed operations of one kind: each term is a sum, over
those operations, of fields of the stats their computing node replied with
(`reply:<dotted.key>`, `*` for every key of a table) or of the counters
each operation moved (`counter:<name>`). Nothing to read: None."""


def _pick(table, dotted: str) -> float:
    keys = dotted.split(".")
    for key in keys[:-1]:
        table = (table or {}).get(key)
    if not isinstance(table, dict):
        return 0.0
    if keys[-1] == "*":
        return float(sum(v for v in table.values()
                         if isinstance(v, (int, float))))
    return float(table.get(keys[-1]) or 0.0)


def _term(record: dict, route: str, term: str) -> float:
    source, _, key = term.partition(":")
    if source == "reply":
        return _pick(record["replies"].get(route), key)
    if source == "counter":
        return float(record["counters"].get(key, 0))
    raise ValueError(f"unknown term {term!r}")


def read(args: dict, run, trace):
    records = [r for r in run.ops if r["op"] == args["op"]
               and not r["error"] and args["route"] in r["replies"]]
    if not records:
        return None
    num = sum(_term(r, args["route"], t) for r in records
              for t in args["numerator"])
    den = sum(_term(r, args["route"], t) for r in records
              for t in args["denominator"])
    if den <= 0:
        return None
    return float(args.get("scale", 1)) * num / den
