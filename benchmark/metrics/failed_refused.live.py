ALIAS_OF = "failed_refused"
