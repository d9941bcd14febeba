"""A ratio of sums over the whole window, of the program's spans and
counters together. A term is `counter:<name>` (the window's movement of a
counter), `span_s:<name>` (summed seconds of the spans of that name that
finished inside the window) or `span_n:<name>` (how many did); a leading
`-` subtracts it. `emit`, where given, names spans whose count, seconds
and seconds per unit of the denominator go out as one line of their own
(`{"phase": <emit_as>, ...}`): the parts the value is made of. A
denominator that did not move: None."""


def _term(run, term: str) -> float:
    sign = -1.0 if term.startswith("-") else 1.0
    source, _, name = term.lstrip("-").partition(":")
    if source == "counter":
        return sign * float(run.counters.get(name, 0))
    count, seconds = run.spans.get(name, (0, 0.0))
    if source == "span_s":
        return sign * float(seconds)
    if source == "span_n":
        return sign * float(count)
    raise ValueError(f"unknown term {term!r}")


def read(args: dict, run, trace):
    den = sum(_term(run, t) for t in args["denominator"])
    if den <= 0:
        return None
    num = sum(_term(run, t) for t in args["numerator"])
    if args.get("emit"):
        parts = {}
        for name in args["emit"]:
            count, seconds = run.spans.get(name, (0, 0.0))
            parts[name] = {"count": count, "seconds": seconds,
                           "per_unit": seconds / den}
        run.emit({"phase": args["emit_as"], "units": den, "parts": parts})
    return float(args.get("scale", 1)) * num / den
