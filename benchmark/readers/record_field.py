"""A number the runner's worker already holds: ``params.field`` of the
record's ``fields`` (times ``params.scale``, default 1). Nothing there, or a
runner that does not report it: no metric."""


def read(ctx, params):
    value = ctx["fields"].get(params["field"])
    if value is None:
        return None
    return value * params.get("scale", 1)
