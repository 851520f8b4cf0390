"""Per cent of the first batch's (token, slot) assignments that no expert
computed, as the program's own loss function counts it (the family's
`check_against_reference` records it). The sorted path has no capacity:
0."""


def read(record):
    routing = record["loop"]["reference_check"].get("program_routing")
    if not routing or "moe_dropped_frac" not in routing:
        return None
    return 100.0 * routing["moe_dropped_frac"]
