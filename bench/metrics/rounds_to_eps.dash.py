"""Mean rounds an early-stopped slot of the Q1 and Q6 templates witnessed
before its interval was narrow enough.  Single-day panels are left out:
they are the scan-bound tail, not the estimator's work."""
TEMPLATES = ("q1", "q6")


def read(record, trace):
    r = [a["rounds_witnessed"] for p in record["panels"]
         if p["template"] in TEMPLATES for a in p.get("answers", [])
         if a["converged"]]
    return sum(r) / len(r) if r else None
