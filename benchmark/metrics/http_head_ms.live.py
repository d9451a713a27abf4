ALIAS_OF = "http_head_ms"
