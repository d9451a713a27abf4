ALIAS_OF = "http_body_ms"
