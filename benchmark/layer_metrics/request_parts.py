"""The statistics verb's four parts of a request (``facts["server"]``: count
and ns of ``success``, ``queue`` and ``compute_infer`` over the window), as
the program's request timeline fills them: ``queue`` counts once a request,
with 0 ns where the model has no queue. A program without the timeline never
counts ``queue`` for these models, and its ``compute_infer`` is the whole of
``model.execute``, the wait included; so without a ``queue`` count there is
nothing to read."""


def mean_ms(facts, part):
    """Mean ms a request of ``part`` (``success``, ``queue``,
    ``compute_infer``); ``None`` where the program does not fill the parts."""
    server = facts.get("server")
    if not server or not server.get("queue_count") or not server.get("success_count"):
        return None
    if not server.get(part + "_count"):
        return None
    return server[part + "_ns"] / server[part + "_count"] / 1e6
