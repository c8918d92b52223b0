"""Host seconds of the plan build spent in Alg 2: the sampled schedule
statistics and the compilation of the scheduled layers' tables, over
every bucket's plan (the ``schedule_stats`` and ``tables`` phases of
``SpectralServer.stats()["plan_phase_s"]``).  None where the program
does not count its plan phases."""


def read(ctx):
    phase_s = (ctx.get("stats") or {}).get("plan_phase_s")
    if not phase_s:
        return None
    return phase_s.get("schedule_stats", 0.0) + phase_s.get("tables", 0.0)
