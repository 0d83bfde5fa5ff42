"""Traffic generators, one per kind of traffic; a mix is a data file under
`traffic/` that names its kind."""
