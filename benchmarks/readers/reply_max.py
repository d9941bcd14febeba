"""The largest value of one field over the timed operations of the window:
the field (`key`, a path of keys given as a list, so a stage named
`d2h+mxu` needs no escaping) of the stats the computing node replied with
on any of `routes` (the routes choose the kinds: only a rebuild or a
repair replies on `/admin/ec/rebuild`). Beside its value it prints one
line `{"phase": "stage_max", ...}` naming the operation that held it: its
place among the window's timed operations, its kind and its wall. No
reply carries the field (a tree older than the field): None."""


def _pick(table, key: list):
    for part in key:
        if not isinstance(table, dict):
            return None
        table = table.get(part)
    return table if isinstance(table, (int, float)) else None


def read(args: dict, run, trace):
    held = None
    for index, record in enumerate(run.ops):
        if record["error"]:
            continue
        for route in args["routes"]:
            value = _pick(record["replies"].get(route), args["key"])
            if value is not None and (held is None or value > held[0]):
                held = (value, index, route, record)
    if held is None:
        return None
    value, index, route, record = held
    scaled = float(args.get("scale", 1)) * value
    run.emit({"phase": "stage_max", "key": args["key"], "value": scaled,
              "op_index": index, "op": record["op"], "route": route,
              "wall_s": record["wall_s"], "ops": len(run.ops)})
    return scaled
