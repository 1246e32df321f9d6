"""Read-modify-write reads per 100 host page ops, in %: 100 x the
``rmw_reads`` counters over the ``host_ops`` counters of the query's own
``ssd.translate`` spans (the preconditioning is a span of its own and
counts neither).  None where no translation span carries the counter."""


def read(r):
    spans = [st for n, _, _, st in r.program_spans
             if n == "translate" and "rmw_reads" in st]
    host = sum(int(st.get("host_ops", 0)) for st in spans)
    if not host:
        return None
    return 100.0 * sum(int(st["rmw_reads"]) for st in spans) / host
