select c_mktsegment, count(*), sum(c_acctbal)
from customer
where c_nationkey = {k}
group by c_mktsegment order by c_mktsegment
