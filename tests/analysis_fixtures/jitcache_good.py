"""Known-good jit-cache fixture: bucketing evidence in the caller, an
entry point defined in-module, and a ``self.*`` method receiver.  Must
produce zero findings."""
from repro.core import ops
from repro.core.offload import run_slots, slot_layout


def compact_all(runs):
    runs = slot_layout([runs], None, *run_slots([len(r) for r in runs]))
    merged = ops.merge_runs(runs)
    return ops.sort_tuples(merged)


def build_image(blocks):
    return blocks


def local_entry(blocks):
    return build_image(blocks)          # defined in this module: exempt


class Engine:
    def run(self, blocks):
        return self.build_image(blocks)  # self receiver: buckets internally

    def build_image(self, blocks):
        return blocks
