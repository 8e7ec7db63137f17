"""What the healers of the set-up's bootstrap heal say of it: the
``heal_stats`` the program puts on each ``heal_end`` event
(``HTTPTransport.recv_checkpoint_multi``; ``docs/heal_plane.md`` "Stages"),
which the worker keeps as ``heal_events``. At step 0 every group but the
bootstrap source heals once, so a four-group run has three."""


def of_healers(run, *path):
    """The value at ``path`` inside each healer's ``heal_stats``, for the
    healers that report one (a program older than a field has none)."""
    out = []
    for r in run.results:
        for event in r.get("heal_events") or []:
            value = event.get("heal_stats")
            for key in path:
                value = value.get(key) if isinstance(value, dict) else None
            if value is not None:
                out.append(value)
    return out
