"""Share of the owners' sorted slots that hold a record, in percent:
records routed over owner slots sorted, summed over the mines and
modes of the run from the program's ``distributed_shuffle_records_total``
and ``distributed_shuffle_slots_total`` counters (the driver's facts);
None from a program without them."""


def read(trace, facts, peaks):
    slots = facts.get("shuffle_slots")
    if not slots or facts.get("shuffle_records") is None:
        return None
    return 100.0 * facts["shuffle_records"] / slots
