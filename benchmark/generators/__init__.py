"""Traffic generators. A traffic file (``benchmark/traffic/<name>.json``)
names one of these modules under ``generator`` and carries its parameters;
the module's ``build(params, model, system, seed, seconds)`` returns the
schedule the runner named in the file drives. A new mix is a new data
file; a new kind of mix is a new module here."""
