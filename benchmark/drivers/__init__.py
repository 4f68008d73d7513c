"""One module per driver, found by the name a traffic file gives:
`run(harness) -> Outcome`."""
