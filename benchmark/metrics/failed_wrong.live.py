ALIAS_OF = "failed_wrong"
