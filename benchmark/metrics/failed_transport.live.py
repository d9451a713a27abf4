ALIAS_OF = "failed_transport"
