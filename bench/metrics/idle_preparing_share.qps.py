"""Share of the traced window, in percent, in which no operation ran on the
device while the serve runtime's dispatcher was inside ``serve.pack`` or
``serve.launch``: device time lost to packing and launching batches."""
import spans


def read(run):
    return spans.idle_preparing_share(run.trace)
