"""Mean duration of the program's ``engine.lower`` spans (a program-cache
miss in ``compile_program``: the compile walk and the VM lowering) in the
traced window, in ms."""
from chipbench import serve_path


def read(run):
    got = serve_path.spans(run)
    if got is None or "engine.lower" not in got["spans"]:
        return None
    rec = got["spans"]["engine.lower"]
    return rec["total_s"] / rec["count"] * 1e3 if rec["count"] else None
