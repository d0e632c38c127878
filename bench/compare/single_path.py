"""Answer comparison for ``"semantics": "single_path"``: a read's pairs,
and the pairs its witnesses are for, must be exactly the reference
relation's row of its source; each witness must be a path of the graph,
from the source to the target, whose label string the reference CYK
derives from the start symbol."""
from bench.reference.cyk import cyk


def compare(edges, config: dict, reads, rows: dict) -> dict:
    """Counts of the reads, and of the witnesses, that the reference does
    not bear out."""
    cnf = config["grammar"]["reference_cnf"]
    start = config["grammar"]["start"]
    answers = witnesses = 0
    for r in reads:
        want = rows.get(r.source, set())
        answers += r.pairs != want or set(r.paths or ()) != want
        witnesses += sum(
            not witness_ok(edges, cnf, start, i, j, path)
            for (i, j), path in (r.paths or {}).items()
        )
    return {"answers": answers, "witnesses": witnesses}


def witness_ok(edges, cnf, start, i, j, path) -> bool:
    at = i
    for e in path:
        if tuple(e) not in edges or e[0] != at:
            return False
        at = e[2]
    return at == j and cyk(cnf, start, [e[1] for e in path])
