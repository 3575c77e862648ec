"""Device self time a round under the scope ``mesh.collective`` (the
round's masked collective: the all_gather or psum of the uploads and the
per-slot mean around it), the mean over the mesh's chips, from the
device trace (``trace_scopes``).  Silent where no op carries the scope."""


def read(rec: dict) -> float | None:
    scopes = rec.get("scopes")
    if not scopes or scopes["scope_s"].get("mesh.collective", 0.0) <= 0.0:
        return None
    return 1e3 * scopes["scope_s"]["mesh.collective"]
