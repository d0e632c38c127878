"""Answer comparison for ``"semantics": "relational"``: a read's pairs
must be exactly the reference relation's row of its source."""


def compare(edges, config: dict, reads, rows: dict) -> dict:
    """Counts of the reads that the reference does not bear out."""
    return {"answers": sum(r.pairs != rows.get(r.source, set())
                           for r in reads)}
