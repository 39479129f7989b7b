"""Seconds of the export's calibration forward: the program's own
``export.calibrate`` span (a wall-clock ``Tracer`` span in core/export.py)."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == 'export.calibrate']
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans)
