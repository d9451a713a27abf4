ALIAS_OF = "http_outside_ms"
