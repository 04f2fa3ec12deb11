"""Host milliseconds per point to build a bucket (``dse.bucket.build``
spans): each point's program made and packed, the bucket stacked and
uploaded with its depths and credits."""
from bench.program_spans import window_spans


def read(view):
    builds = [r for r in window_spans(view) or ()
              if r.name == "dse.bucket.build"]
    points = sum(r.attrs.get("points", 0) for r in builds)
    if not points:
        return None
    return sum(r.seconds for r in builds) / points * 1e3
