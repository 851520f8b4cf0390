"""The busiest expert's load over the mean load, for the first batch's
routing at the seed's weights, as the program's own loss function counts
it (the family's `check_against_reference` records it): 1.0 is perfect
balance, 64 is every token on one expert."""


def read(record):
    routing = record["loop"]["reference_check"].get("program_routing")
    return routing and routing.get("moe_load_max_over_mean")
