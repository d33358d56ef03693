"""A share, in %: the sum over the finished requests of one field of the
program's per-request record (``facts["requests"][i]["record"]``) over the
sum of several. Parameters: ``field`` and ``of`` (the list of fields whose
sum is the whole). Only requests whose record has every one of them count.
No such request, or a whole of 0: None."""


def read(spec: dict, facts: dict):
    part = whole = 0.0
    for req in facts.get("requests") or ():
        record = req.get("record") or {}
        fields = [record.get(k) for k in [spec["field"], *spec["of"]]]
        if not req.get("ok") or any(v is None for v in fields):
            continue
        part += fields[0]
        whole += sum(fields[1:])
    if not whole:
        return None
    return 100.0 * part / whole
