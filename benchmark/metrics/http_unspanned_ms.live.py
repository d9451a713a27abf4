ALIAS_OF = "http_unspanned_ms"
