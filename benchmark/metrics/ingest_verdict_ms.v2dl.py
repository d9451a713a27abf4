ALIAS_OF = "ingest_verdict_ms"
