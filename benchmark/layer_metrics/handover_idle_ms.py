"""Device idle a batch whose innermost span is the router's hand-over:
`serving.complete` (records, histograms, futures), `serving.assemble`
(queue-wait records, stacking the feeds) or `serving.form_batch` (waiting
for requests). The run prints the three apart."""

from benchmark.harness import sections


def read(run):
    return sections.handover_idle_ms(run)
